"""DeepSeek-V3.2's block (models/mla.py with ``index_topk`` > 0: an indexer
scores every cached token and a query attends to the rows it picked) at a
small size with widths aligned to nothing: the block against the plain
reference with fewer rows kept than the context holds, prefill windows and
decode through BOTH pools, the chosen sets themselves, a context that keeps
every row against dense latent attention, the exact selection, the grouped
router, the YaRN table, a chip's share of the experts, each kernel against
its plain form, the mapping of the published keys, and the engine end to end
with its counters."""

import asyncio
import dataclasses
import functools
import importlib.util
import json
import math
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import latent_table_cases as table_cases
from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.kvcache import pages, state
from llm_d_inference_scheduler_tpu.models import configs, family, mla
from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
from llm_d_inference_scheduler_tpu.models.routing import route
from llm_d_inference_scheduler_tpu.ops import (pallas_dsa,
                                               pallas_latent_attention,
                                               sparse_attention)
from llm_d_inference_scheduler_tpu.ops.rope import (rope_table, yarn_frequencies,
                                                    yarn_mscale)

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = dataclasses.replace(configs.get_config("tiny-dsa"), dtype="float32")
TOPK = CFG.index_topk
# float32 on both sides, different summation order (test_reference.py's).
TOL = dict(rtol=2e-4, atol=2e-4)
TABLES = jnp.asarray([[3, 1, 5, 7, 0, 0], [2, 6, 4, 8, 0, 0]], jnp.int32)


def _reference():
    path = REPO / "chipbench" / "configs" / "reference_deepseek_v32.py"
    spec = importlib.util.spec_from_file_location("reference_deepseek_v32",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(cfg):
    return dict(n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                rope_theta=cfg.rope_theta, rope_yarn=cfg.rope_yarn,
                norm_eps=cfg.norm_eps,
                experts_per_token=cfg.experts_per_token,
                routed_scaling_factor=cfg.routed_scaling_factor,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                index_n_heads=cfg.index_n_heads,
                index_head_dim=cfg.index_head_dim, index_topk=cfg.index_topk,
                first_expert=cfg.experts_first)


def _share(params, cfg, rank, held):
    """(cfg, params) of the chip that holds experts rank * held .. of every
    expert layer."""
    layers = dict(params["layers"])
    for name in ("w1", "w2", "w3"):
        layers[name] = layers[name][:, rank * held:(rank + 1) * held]
    return (dataclasses.replace(cfg, experts_held=held,
                                experts_first=rank * held),
            {**params, "layers": layers})


@functools.lru_cache(maxsize=None)
def _fixture(held=0, rank=0):
    """The whole model (``held`` 0), or a chip's share of its experts: two
    sequences of 64 tokens, the whole forward's logits, rows and choices."""
    params = mla.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    cfg = CFG
    if held:
        cfg, params = _share(params, CFG, rank, held)
    tokens = jax.random.randint(jax.random.key(9), (2, 64), 0, CFG.vocab_size)
    logits, (fresh, _), (routes, picked) = jax.jit(functools.partial(
        mla.forward, cfg=cfg, want_kv=True, want_routes=True))(
            params, tokens=tokens)
    return cfg, params, tokens, logits, fresh, routes, picked


def _cache_with(fresh, n_tokens):
    """A cache whose two pools hold the first ``n_tokens`` rows and keys of
    both sequences."""
    geom = pages.PageGeometry.for_engine(CFG, 2, 96)
    assert geom.shape == (3, 13, 16, 128) and geom.index_shape == (3, 13, 16, 16)
    assert geom.counted and not geom.counts_zero
    cache, none = pages.alloc(geom)
    assert none is None and cache.ssm is None and cache.v is None
    assert cache.idx.shape == geom.index_shape
    bucket = -(-n_tokens // 16) * 16    # a prefill hands over whole pages
    cut = dataclasses.replace(fresh, k=fresh.k[:, :, :bucket],
                              idx=fresh.idx[:, :, :bucket])
    cache, _ = pages.write_sequences(
        state.at_slots(cache, [0, 1]), None, cut, None, TABLES,
        jnp.asarray([n_tokens] * 2))
    return cache


# ---------- the block against the plain reference ----------

def test_family_geometry_and_what_names_the_block():
    assert family(CFG) is mla and CFG.tallies_choices
    assert CFG.latent_dim == 32 and CFG.index_dim == 16
    assert CFG.n_kv_layers == 3 and CFG.n_expert_layers == 2
    kimi = configs.get_config("tiny-mla")
    assert kimi.index_dim == 0 and not kimi.tallies_choices
    geom = pages.PageGeometry.for_engine(kimi, 2, 64)
    assert geom.index_shape is None and geom.index_pool_bytes == 0


@pytest.mark.parametrize("held,rank", [(0, 0), (4, 2)])
def test_forward_matches_the_plain_reference_and_picks_its_rows(held, rank):
    """64 tokens, 24 rows kept: every query past the 24th selects."""
    cfg, params, tokens, logits, fresh, routes, picked = _fixture(held, rank)
    ref = _reference()
    want_hidden, ref_routes, _ = ref.hidden(params, tokens[1], q_block=20,
                                            **_sizes(cfg))
    np.testing.assert_allclose(np.asarray(logits[1]),
                               np.asarray(ref.logits(params, want_hidden)),
                               **TOL)
    ours = routes.reshape(routes.shape[0], 2, -1, cfg.experts_per_token)
    assert (np.sort(np.asarray(ours[:, 1]), -1)
            == np.sort(np.asarray(ref_routes), -1)).all()
    # The sets: min(24, t + 1) rows a query, never a later one.
    kept = np.asarray(picked[:, 1])                       # [L, S, S]
    assert (kept.sum(-1) == np.minimum(np.arange(64) + 1, TOPK)).all()
    assert not np.triu(kept, 1).any()
    assert not kept[:, 40:].all(axis=0)[:, :17].all()     # some early row lost
    # Held to the program's sets the reference says they ARE its own.
    _, _, shared = ref.hidden(
        params, tokens[1], q_block=20, **_sizes(cfg),
        picked=lambda layer, lo, hi: kept[layer, lo:hi])
    assert shared == [1.0, 1.0, 1.0]
    chose = np.asarray(routes)
    first, count = cfg.held_experts
    assert int(fresh.held) == ((chose >= first) & (chose < first + count)).sum()


@pytest.mark.parametrize("kernels", [False, True])
def test_windows_then_decode_through_both_pools(kernels):
    """A first window of 16, three windows that continue it through the
    pages (16 + 3 x 16 = 64 tokens, selecting from the second on), then four
    decode steps: the logits and the sets of the whole forward, with the
    plain forms and with both kernels interpreted."""
    cfg, params, tokens, logits, fresh, _, picked = _fixture()
    if kernels:
        cfg = dataclasses.replace(cfg, index_impl="kernel_interpret",
                                  expanded_impl="kernel_interpret")
    cache = _cache_with(fresh, 16)
    step = jax.jit(functools.partial(mla.prefill_with_prefix, cfg=cfg,
                                     want_routes=True))
    for w in range(1, 4):
        for b in range(2):
            got, cache, _, (_, kept) = step(
                params, tokens=tokens[b:b + 1, 16 * w:16 * (w + 1)],
                suffix_len=jnp.asarray([16]), prefix_len=jnp.asarray([16 * w]),
                k_pages=state.at_slots(cache, [b]), v_pages=None,
                block_table_row=TABLES[b:b + 1],
                prior_table_row=TABLES[b:b + 1, :w])
            cache, *_ = state.take_counts(cache)
            np.testing.assert_allclose(np.asarray(got[0]),
                                       np.asarray(logits[b, 16 * w + 15]),
                                       **TOL)
            # [L, 1, 16, prior 16 w + own 16] against the whole forward's.
            assert (np.asarray(kept[:, 0])
                    == np.asarray(picked[:, b, 16 * w:16 * (w + 1),
                                         :16 * (w + 1)])).all()
    # Decode: teacher-forced from 48 tokens cached (the pools hold 64: the
    # steps rewrite rows 48.. as they go, which is what they held).
    attend = functools.partial(pages.latent_decode_attention, kernel=kernels,
                               interpret=kernels)
    decode = jax.jit(functools.partial(mla.decode_step, cfg=cfg,
                                       attention_fn=attend, want_routes=True))
    for t in range(48, 52):
        got, cache, _, (_, kept) = decode(
            params, tokens=tokens[:, t], positions=jnp.asarray([t, t]),
            k_pages=state.at_slots(cache, [0, 1]), v_pages=None,
            block_tables=TABLES)
        cache, *_ = state.take_counts(cache)
        np.testing.assert_allclose(np.asarray(got), np.asarray(logits[:, t]),
                                   **TOL)
        kept = np.asarray(kept)                   # [L, B, 96 cached + 1 own]
        assert (kept[:, :, :t] == np.asarray(picked[:, :, t, :t])).all()
        assert (kept[:, :, 96] == np.asarray(picked[:, :, t, t])).all()
        assert not kept[:, :, t:96].any()


def test_a_context_that_keeps_every_row_is_dense_latent_attention():
    """index_topk at or above the context: the same weights through the
    block without an indexer give the same logits, in every step form (the
    tie to Kimi's path); and a program whose rows cannot outnumber
    index_topk never scores."""
    _, params, tokens, _, _, _, _ = _fixture()
    wide = dataclasses.replace(CFG, index_topk=64)
    dense = dataclasses.replace(CFG, index_topk=0)
    run = lambda cfg: jax.jit(functools.partial(  # noqa: E731
        mla.forward, cfg=cfg, want_kv=True))(params, tokens=tokens)
    got, (fresh, _) = run(wide)
    want, (rows, _) = run(dense)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    np.testing.assert_allclose(np.asarray(fresh.k), np.asarray(rows), **TOL)
    mask = jnp.ones((1, 64, 64), bool)
    assert mla._selected(wide, None, jnp.zeros((1, 64, 16)), mask) is mask
    # Decode, 40 cached rows of a table 96 wide (the selection runs and
    # keeps them all) against the dense block's decode step.
    cache = _cache_with(fresh, 40)
    plain, _ = pages.alloc(pages.PageGeometry.for_engine(dense, 2, 96))
    plain, _ = pages.write_sequences(plain, None, rows[:, :, :48], None,
                                     TABLES, jnp.asarray([40, 40]))
    args = dict(tokens=tokens[:, 40], positions=jnp.asarray([40, 40]),
                v_pages=None, block_tables=TABLES)
    got, _, _ = mla.decode_step(params, wide, **args,
                                k_pages=state.at_slots(cache, [0, 1]))
    want, _, _ = mla.decode_step(params, dense, **args, k_pages=plain)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# ---------- the selection ----------

@pytest.mark.parametrize("k", [1, 5, 24, 200])
def test_select_top_is_the_stable_sort_with_ties_and_short_rows(k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(6, 7, 130)).astype(np.float32)
    scores[0] = np.round(scores[0])                 # many ties
    scores[1, :, ::3] = 0.0
    scores[1, :, 1::3] = -0.0                       # both zeros are one value
    scores[2] = -np.inf
    scores[3, :, :40] = np.inf
    seen = rng.random((6, 7, 130)) < 0.7
    seen[4, :3] = False                             # a query that sees nothing
    seen[5, :, 3:] = False                          # fewer rows than k
    got = np.asarray(jax.jit(functools.partial(
        sparse_attention.select_top, k=k))(jnp.asarray(scores),
                                           jnp.asarray(seen)))
    want = np.asarray(_reference().selection(
        jnp.asarray(scores).reshape(-1, 130),
        jnp.asarray(seen).reshape(-1, 130), k)).reshape(seen.shape)
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(seen.sum(-1), k)).all()


# ---------- the kernels against their plain forms ----------

@pytest.mark.parametrize("shape", [(1, 48, 200), (3, 1, 96), (2, 16, 600)])
def test_index_scores_kernel_matches_the_plain_form(shape):
    B, S, T = shape
    keys = jax.random.split(jax.random.key(S), 3)
    q = jax.random.normal(keys[0], (B, S, 4, 16), jnp.float32)
    w = jax.random.normal(keys[1], (B, S, 4), jnp.float32)
    k = jax.random.normal(keys[2], (B, T, 16), jnp.float32)
    got = pallas_dsa.index_scores_pallas(q, w, k, interpret=True)
    assert got.shape == (B, S, T) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        sparse_attention.index_scores(q, w, k)), rtol=1e-5, atol=1e-5)


def test_paged_index_scores_kernel_reads_each_lanes_pages_by_its_table():
    """One query a lane against the key pool's pages under the block table:
    a lane with nothing cached, one that ends inside a page, one that fills
    the table; the second layer of the pool."""
    B, Hi, Di, block, width = 3, 4, 16, 16, 6
    ks = jax.random.split(jax.random.key(5), 4)
    pool = jax.random.normal(ks[0], (2, 20, block, Di), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(2).permutation(
        np.arange(1, 19)).reshape(B, width), jnp.int32)
    q = jax.random.normal(ks[1], (B, Hi, Di), jnp.float32)
    w = jax.random.normal(ks[2], (B, Hi), jnp.float32)
    seq_lens = jnp.asarray([1, 41, block * width + 1], jnp.int32)
    got = np.asarray(pallas_dsa.index_scores_paged_pallas(
        q, w, pool, jnp.int32(1), tables, seq_lens, interpret=True))
    want = np.asarray(sparse_attention.index_scores(
        q[:, None], w[:, None], pages.read_rows(pool, 1, tables)))[:, 0]
    assert got.shape == want.shape == (B, block * width)
    for lane, n in enumerate(np.asarray(seq_lens) - 1):
        np.testing.assert_allclose(got[lane, :n], want[lane, :n],
                                   rtol=1e-5, atol=1e-5)
    assert not got[0].any()


@pytest.mark.parametrize("shape", [(1, 4, 40, 200), (2, 8, 16, 1100)])
def test_window_attention_kernel_matches_the_plain_form(shape):
    """Queries and rows that fill no tile, a batch of two, tiles none of
    whose rows is kept (skipped) beside tiles where single queries keep
    nothing, and a query whose only kept row is the last."""
    B, H, S, T = shape
    dn, dr, dv = 16, 8, 12
    ks = jax.random.split(jax.random.key(11), 6)
    q_nope = jax.random.normal(ks[0], (B, H, S, dn), jnp.float32)
    q_rope = jax.random.normal(ks[1], (B, H, S, dr), jnp.float32)
    k_nope = jax.random.normal(ks[2], (B, H, T, dn), jnp.float32)
    k_rope = jax.random.normal(ks[3], (B, T, dr), jnp.float32)
    v = jax.random.normal(ks[4], (B, H, T, dv), jnp.float32)
    keep = np.array(jax.random.uniform(ks[5], (B, S, T)) < 0.3)
    keep[:, :, 128:640] = False          # a whole tile of rows dead
    keep[:, 0] = False
    keep[:, 0, T - 1] = True             # one row, in the last tile
    keep[:, 1, :128] = False             # nothing in the first tile
    keep[:, 1, T - 3:] = True
    kw = dict(scale=0.37)
    want = sparse_attention.masked_window_attention(
        q_nope, q_rope, k_nope, k_rope, v, jnp.asarray(keep), **kw)
    got = pallas_dsa.masked_window_attention_pallas(
        q_nope, q_rope, k_nope, k_rope, v, jnp.asarray(keep), **kw,
        interpret=True)
    assert got.shape == (B, H, S, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[:, :, 0]),
                               np.asarray(v[:, :, T - 1]), rtol=1e-6)


def test_sparse_decode_kernel_matches_the_plain_form_at_its_extremes(
        monkeypatch):
    """Lanes in turn: a whole STAGE with no selected row between two that
    have some, a context shorter than any selection (every row kept), the
    current token not selected, nothing selected but the current token, a
    lane that selected nothing at all (zeros out), and an empty lane."""
    L, N, block, W, H, Dk, value = 2, 40, 16, 128, 4, 40, 24
    keys = jax.random.split(jax.random.key(3), 4)
    pool = jax.random.normal(keys[0], (L, N, block, W), jnp.float32)
    B, maxB = 6, 32                                   # 512 rows a lane
    q = jax.random.normal(keys[1], (B, H, Dk), jnp.float32)
    cur = jax.random.normal(keys[2], (B, Dk), jnp.float32)
    tables = (jnp.arange(B * maxB, dtype=jnp.int32) % (N - 1) + 1).reshape(
        B, maxB)
    # Stages of four pages, so that a lane has several and one can be empty.
    monkeypatch.setattr(pallas_latent_attention, "STAGE_VMEM_BYTES",
                        4 * block * W * 12)
    stage = pallas_dsa.pages_per_stage(block, W, 4, maxB) * block
    assert stage == 64
    seq_lens = jnp.asarray([500, 20, 300, 200, 100, 1])
    keep = np.array(jax.random.bernoulli(keys[3], 0.3, (B, maxB * block)))
    keep[0, stage:2 * stage] = False
    keep[1] = True
    keep[3] = False
    keep[4] = False
    cur_keep = jnp.asarray([True, True, False, True, False, True])
    args = (q, pool, jnp.asarray(1), tables, seq_lens, cur,
            jnp.asarray(keep), cur_keep)
    kw = dict(value_dim=value, scale=0.3)
    want = sparse_attention.sparse_latent_paged_decode_attention(*args, **kw)
    got = pallas_dsa.sparse_latent_paged_decode_attention_pallas(
        *args, **kw, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(want[4]).any() and np.isfinite(np.asarray(got)).all()
    # Every row kept and the current token in: the dense latent attention.
    every = jnp.ones_like(jnp.asarray(keep))
    np.testing.assert_allclose(
        np.asarray(sparse_attention.sparse_latent_paged_decode_attention(
            *args[:6], every, jnp.ones((B,), bool), **kw)),
        np.asarray(pages.latent_decode_attention(*args[:6], **kw)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", table_cases.CASES)
def test_paged_index_scores_kernel_over_tables_with_runs_and_without(
        case, monkeypatch):
    """The indexer's decode walk against the plain form over every kind of
    table (tests/latent_table_cases.py), at a lane's cached rows; stages past
    a lane's length read zero."""
    c = table_cases
    monkeypatch.setattr(pallas_latent_attention, "STAGE_VMEM_BYTES",
                        c.STAGE_VMEM_BYTES)
    Hi, Di = 4, 128
    assert pallas_dsa.pages_per_stage(c.BLOCK, Di, 4, c.WIDTH) == c.STAGE
    tables, lens = c.tables(case, pallas_dsa.run_pages(c.STAGE))
    pool = jnp.asarray(c.pool_under(tables, lens, Di, Di, seed=31))
    ks = jax.random.split(jax.random.key(32), 2)
    q = jax.random.normal(ks[0], (len(lens), Hi, Di), jnp.float32)
    w = jax.random.normal(ks[1], (len(lens), Hi), jnp.float32)
    got = np.asarray(pallas_dsa.index_scores_paged_pallas(
        q, w, pool, jnp.int32(1), jnp.asarray(tables), jnp.asarray(lens),
        interpret=True))
    want = np.asarray(sparse_attention.index_scores(
        q[:, None], w[:, None],
        pages.read_rows(pool, 1, jnp.asarray(tables))))[:, 0]
    assert got.shape == want.shape == (len(lens), c.WIDTH * c.BLOCK)
    for lane, n in enumerate(np.maximum(lens - 1, 0)):
        np.testing.assert_allclose(got[lane, :n], want[lane, :n],
                                   rtol=1e-4, atol=1e-3)
        past = -(-n // (c.STAGE * c.BLOCK)) * c.STAGE * c.BLOCK
        assert not got[lane, past:].any()


@pytest.mark.parametrize("case", table_cases.CASES)
def test_sparse_decode_kernel_over_tables_with_runs_and_without(
        case, monkeypatch):
    """The masked decode walk against the plain form over every kind of
    table, a third of the rows selected: which groups it takes as one copy
    is what was counted by hand, and rows past a lane's length weigh
    nothing."""
    c = table_cases
    monkeypatch.setattr(pallas_latent_attention, "STAGE_VMEM_BYTES",
                        c.STAGE_VMEM_BYTES)
    H, Dk, value, W = 3, 72, 40, 128
    assert pallas_dsa.pages_per_stage(c.BLOCK, W, 4, c.WIDTH) == c.STAGE
    group = pallas_dsa.run_pages(c.STAGE)
    tables, lens = c.tables(case, group)
    runs = np.asarray(pallas_dsa.table_runs(
        jnp.asarray(tables), jnp.asarray(lens), c.BLOCK, group))
    np.testing.assert_array_equal(runs, c.runs_by_hand(tables, lens, group))
    pool = jnp.asarray(c.pool_under(tables, lens, Dk, W, seed=41))
    ks = jax.random.split(jax.random.key(42), 3)
    B = len(lens)
    q = jax.random.normal(ks[0], (B, H, Dk), jnp.float32)
    cur = jax.random.normal(ks[1], (B, Dk), jnp.float32)
    keep = jax.random.bernoulli(ks[2], 0.3, (B, c.WIDTH * c.BLOCK))
    cur_keep = jnp.asarray([True, False, True])
    kw = dict(value_dim=value, scale=0.2)
    want = sparse_attention.sparse_latent_paged_decode_attention(
        q, pool, jnp.asarray(1), jnp.asarray(tables),
        jnp.maximum(jnp.asarray(lens), 1), cur, keep, cur_keep, **kw)
    got = pallas_dsa.sparse_latent_paged_decode_attention_pallas(
        q, pool, jnp.asarray(1), jnp.asarray(tables), jnp.asarray(lens), cur,
        keep, cur_keep, **kw, interpret=True)
    assert np.abs(np.asarray(want)).max() < 10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_the_latent_microbenchmark_rehearses_on_the_cpu(capsys, monkeypatch):
    """scripts/microbench_decode.py --latent at the cell's widths and a small
    point, kernels interpreted: a line a (kernel, table, R), every kernel
    within bf16's rounding of its plain form on tables with runs, without,
    and as the allocator's churn leaves them."""
    spec = importlib.util.spec_from_file_location(
        "microbench_decode", REPO / "scripts" / "microbench_decode.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    # The script's main points JAX's compile cache at the checkout: not from
    # a test worker.
    monkeypatch.setattr("llm_d_inference_scheduler_tpu.utils.compile_cache."
                        "configure_compile_cache", lambda: "")
    bench.main(["--latent", "--latent-interpret", "--latent-points", "2x2300",
                "--latent-max-model-len", "4096", "--latent-prompts",
                "1500-3500", "--latent-outputs", "100-500",
                "--latent-groups", "8", "--latent-iters", "1"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert {(ln["component"], ln["table"]) for ln in lines} == {
        (k, t) for k in ("mla_paged_decode_attention",
                         "dsa_paged_decode_attention",
                         "dsa_index_scores_decode")
        for t in ("shuffled", "churn", "runs")}
    for ln in lines:
        assert ln["R"] == 8 and ln["max_err_vs_plain"] < 0.02, ln
        assert ln["roofline_pct"] is None      # no chip, no share of a peak
    share = {ln["table"]: ln["run_share_pct"] for ln in lines}
    assert share["shuffled"] == 0 and share["runs"] == 100
    assert 30 < share["churn"] < 100


# ---------- the router, the rotary table, the shares ----------

def test_grouped_router_matches_the_reference_and_one_group_is_unchanged():
    cfg, params, _, _, _, _, _ = _fixture()
    lp = {k: v[0] for k, v in params["layers"].items()
          if k in ("router", "router_bias")}
    h = jax.random.normal(jax.random.key(4), (50, cfg.d_model), jnp.float32)
    idx, gates = route(cfg, lp, h)
    _, want, biased, under = _reference().route(
        lp, h, experts_per_token=cfg.experts_per_token, n_group=4,
        topk_group=2)
    # An output in an open group is not under the last open one; one in a
    # closed group is by a finite margin (what a forced choice there costs).
    under, closed = np.asarray(under), np.isinf(np.asarray(biased))
    assert (under[~closed] == 0).all() and (under[closed] >= 0).all()
    assert np.isfinite(under).all() and (under[closed] > 0).any()
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.asarray(want), -1)).all()
    # Every choice lies in one of the token's two open groups of four.
    groups = np.asarray(idx) // 4
    assert all(len(set(g)) <= 2 for g in groups)
    assert np.isinf(np.asarray(biased)).sum() == 50 * 8
    np.testing.assert_allclose(np.asarray(gates.sum(-1)),
                               cfg.routed_scaling_factor, rtol=1e-5)
    # Some token's plain best three span more groups: the limit binds.
    plain_cfg = dataclasses.replace(cfg, n_group=1, topk_group=1)
    plain, _ = route(plain_cfg, lp, h)
    assert (np.sort(np.asarray(plain), -1) != np.sort(np.asarray(idx), -1)).any()
    scores = jax.nn.sigmoid(h @ lp["router"]) + lp["router_bias"]
    assert (np.asarray(plain) == np.asarray(
        jax.lax.top_k(scores, cfg.experts_per_token)[1])).all()


def test_yarn_table_is_the_formula_and_no_scaling_is_unchanged():
    d, theta = 64, 1e4
    yarn = (40.0, 4096.0, 32.0, 1.0, 1.0)
    got = np.asarray(yarn_frequencies(d, theta, yarn))
    low = math.floor(d * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(theta)))
    high = math.ceil(d * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    i = np.arange(d // 2)
    f = theta ** (-2.0 * i / d)
    g = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, f * (1 - g) + f / 40 * g, rtol=1e-6)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)   # kept whole
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(_reference().yarn_inv_freq(
        d, theta, yarn)), got, rtol=1e-6)
    assert abs(yarn_mscale(yarn) - 1.3689) < 1e-4 and yarn_mscale(()) == 1.0
    pos = jnp.arange(300)
    for a, b in zip(rope_table(pos, d, theta), rope_table(pos, d, theta, ())):
        assert (np.asarray(a) == np.asarray(b)).all()
    cos, _ = rope_table(pos, d, theta, yarn)
    np.testing.assert_allclose(np.asarray(cos), np.cos(
        np.arange(300)[:, None] * got[None]), atol=1e-4)
    # The softmax scale carries the magnitude factor squared.
    assert mla._scale(CFG) == pytest.approx(28 ** -0.5 * yarn_mscale(CFG.rope_yarn) ** 2)
    assert mla._scale(configs.get_config("tiny-mla")) == 28 ** -0.5


def test_the_shares_of_all_ranks_and_the_shared_expert_once_are_the_layer():
    """Four chips hold four experts each: their expert layers' outputs, the
    shared expert's taken once, add up to the uncut layer's."""
    cfg, params, _, _, _, _, _ = _fixture()
    h = jax.random.normal(jax.random.key(5), (40, cfg.d_model), jnp.float32)
    layer = lambda p: {k: v[1] for k, v in p["layers"].items()}  # noqa: E731
    whole, chose, _ = mla._ffn(cfg, layer(params), h)
    shared = mla._swiglu(h, *(layer(params)[k] for k in ("w1s", "w3s", "w2s")))
    total, held = 0.0, 0
    for rank in range(4):
        c, p = _share(params, cfg, rank, 4)
        y, again, counts = mla._ffn(c, layer(p), h)
        assert (np.asarray(again) == np.asarray(chose)).all()
        total = total + (y - shared)
        held += int(counts[0])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               **TOL)
    assert held == 40 * cfg.experts_per_token


# ---------- the published keys ----------

def _published():
    with open(REPO / "chipbench" / "configs" / "deepseek-v3.2-exp-cut.json") as f:
        doc = json.load(f)
    return {k: v for k, v in doc.items()
            if k not in ("source", "reduced", "assumed", "departures",
                         "deployment", "serve", "reference")}


def test_config_from_hf_maps_the_cells_file():
    cfg = config_from_hf(types.SimpleNamespace(**_published()), name="cut")
    assert cfg == configs.ModelConfig(
        name="cut", vocab_size=16160, d_model=7168, n_layers=5, n_heads=128,
        n_kv_heads=128, d_ff=18432, rope_theta=1e4, max_seq_len=163840,
        norm_eps=1e-6, n_experts=256, experts_per_token=8, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense=1, moe_d_ff=2048, n_shared_experts=1,
        routed_scaling_factor=2.5, experts_held=16, experts_first=0,
        q_lora_rank=1536, n_group=8, topk_group=4,
        rope_yarn=(40.0, 4096.0, 32.0, 1.0, 1.0), index_topk=2048,
        index_n_heads=64, index_head_dim=128)
    assert family(cfg) is mla and cfg.n_kv_layers == 5
    assert cfg.held_experts == (0, 16) and cfg.n_expert_layers == 4
    geom = pages.PageGeometry.for_engine(cfg, 32, 18432)
    assert geom.max_blocks_per_seq == 1152
    assert geom.token_bytes == 1280 and geom.index_token_bytes == 256
    assert geom.shape[2:] == (16, 640) and geom.index_shape[2:] == (16, 128)


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("norm_topk_prob", False),
    ("rope_scaling", {"type": "linear", "factor": 4}),
    ("rope_scaling", {"type": "yarn", "factor": 40, "mscale": 0.7,
                      "mscale_all_dim": 1.0,
                      "original_max_position_embeddings": 4096}),
    ("n_group", 7), ("topk_group", 9), ("index_n_heads", None),
    ("q_lora_rank", None), ("topk_method", "greedy")])
def test_config_from_hf_still_refuses_what_is_not_built(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf(types.SimpleNamespace(**{**_published(), key: value}))


# ---------- the engine ----------

@pytest.fixture
def served():
    """tiny-dsa in float32, a chip's share of it (8 of 16 experts from
    expert 4 on), under a name of its own."""
    name = "tiny-dsa-f32"
    configs._REGISTRY[name] = dataclasses.replace(
        CFG, name=name, experts_held=8, experts_first=4)
    yield name
    del configs._REGISTRY[name]


def _counters(eng, name, label):
    return {s.labels[label]: s.value
            for m in eng.telemetry.registry.collect() for s in m.samples
            if s.name == name}


def test_engine_serves_through_windows_both_pools_and_the_prefix_cache(served):
    """Prompts in windows of 32 (the third window of the long one selects),
    both kernels interpreted, decode past index_topk, and a rerun that finds
    BOTH pools' pages in the prefix cache: greedy tokens are the plain
    forward's, and the counters hold what the positions say."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    long = [1] + [(j * 17) % 450 + 3 for j in range(70)]
    short = [1] + [(j * 5) % 450 + 3 for j in range(14)]

    async def serve(cfg):
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            async def one(rid, prompt, n):
                out = eng.submit(EngineRequest(
                    request_id=rid, prompt_token_ids=list(prompt),
                    max_tokens=n, temperature=0.0, ignore_eos=True))
                toks, cached = [], 0
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=300)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                        cached = max(cached, ev.cached_tokens or 0)
                    if ev.finish_reason is not None:
                        return toks, cached

            first = await asyncio.gather(one("L", long, 6), one("S", short, 5))
            free = eng.allocator.reusable_blocks
            again = await one("L2", long, 6)
            with pytest.raises(ValueError, match="latent"):
                eng.submit(EngineRequest(
                    request_id="pd", prompt_token_ids=short,
                    kv_transfer_params={"do_remote_decode": True}))
            # The whole forward, teacher-forced along the long request's own
            # tokens: every greedy token of the served stream is its argmax.
            told = jnp.asarray([long + first[0][0]])
            logits = jax.jit(lambda p, t: mla.forward(p, eng.mcfg, t)[0])(
                eng.params, told)
            plain = [int(logits[0, len(long) - 1 + i].argmax())
                     for i in range(6)]
            return (first, again, plain, free, eng.allocator.reusable_blocks,
                    _counters(eng, "jetstream:dsa_query_tokens_total", "form"),
                    _counters(eng, "jetstream:dsa_rows_total", "kind"),
                    _counters(eng, "jetstream:mla_attention_tokens_total",
                              "form"),
                    eng.describe()["settings"])
        finally:
            await eng.stop()

    ((lw, sw), again, plain, free, free_after, queries, rows, attn,
     settings) = asyncio.run(serve(
        EngineConfig(model=served, backend="tpu", max_batch=2,
                     max_model_len=128, decode_chunk=4, kv_events_port=0,
                     seed=7, prefill_chunk=32, pallas_attention=True,
                     pallas_interpret=True)))
    assert lw[0] == plain and len(sw[0]) == 5
    # The rerun found its first two windows cached -- latent rows AND indexer
    # keys, under the same page ids -- and selected among them as before.
    assert again[0] == lw[0] and again[1] >= 64
    assert free_after == free         # released together: one allocator
    assert queries["selected"] > 0 and queries["all"] > 0
    # The long prompt alone: contexts 1..71, of which 25..71 select.
    assert queries["selected"] >= 71 - TOPK
    assert 0 < rows["attended"] < rows["scored"]
    assert attn["expanded"] > 0 and attn["absorbed"] > 0
    assert settings["index_topk"] == TOPK
    assert settings["kv_token_bytes"] == 128 * 4          # the latent row's
    assert settings["index_token_bytes"] == 16 * 4
    assert settings["index_pool_bytes"] == 3 * 17 * 16 * 16 * 4
    assert settings["index_scores"] == "kernel_interpret"
    assert settings["kv_run_pages"] == pallas_latent_attention.RUN_PAGES
    assert settings["prefix_caching"] and settings["pallas_attention"]


def test_engine_refuses_what_the_second_pool_cannot_do(served):
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    for extra in (dict(tp_size=2), dict(role="prefill"), dict(pp_size=2)):
        with pytest.raises(ValueError, match="indexer's key pool"):
            TpuEngine(EngineConfig(model=served, backend="tpu", max_batch=2,
                                   max_model_len=64, kv_events_port=0,
                                   **extra))


@pytest.mark.parametrize("steps", [4, 2])
def test_selection_counters_from_positions(steps):
    """What _device_call books of a selecting block's programs (the engine's
    _requests_part, the family's program_counts, the telemetry's
    book_program) against a count by hand: a decode chunk (of the steps it
    was dispatched with, whatever decode_chunk says), a continuation window,
    and two ops that put nothing through the block."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
    from llm_d_inference_scheduler_tpu.engine.telemetry import EngineTelemetry
    from llm_d_inference_scheduler_tpu.models import bind

    eng = object.__new__(TpuEngine)
    eng.cfg = EngineConfig(model="tiny-dsa", max_batch=4, decode_chunk=4)
    eng.bound = bind(CFG, platform="cpu")
    eng.telemetry = EngineTelemetry(block_size=16, num_blocks=8)
    for op, args in (
            (("decode",), dict(
                positions=np.asarray([21, 40, 0, 0], np.int32),
                slots=np.asarray([0, 2, 4, 4], np.int32), steps=steps)),
            (("prefix_prefill", 16, 2), dict(
                tokens=np.zeros((1, 16), np.int32),
                slots=np.asarray([1], np.int32),
                prefix_len=np.asarray([16], np.int32),
                suffix_len=np.asarray([12], np.int32))),
            (("embed", 16), dict(tokens=np.zeros((1, 16), np.int32))),
            (("prefill", 16), dict(          # a warm-up program: nobody's
                tokens=np.zeros((1, 16), np.int32), warm=True,
                slots=np.asarray([4], np.int32),
                seq_len=np.asarray([1], np.int32)))):
        real, queries = eng._requests_part(op, args)
        eng.telemetry.book_program(eng.bound.program_counts(
            op[0], args["slots" if op[0] == "decode" else "tokens"].size,
            args.get("steps", 1), real=real, queries=queries))
    contexts = ([22, 23, 24, 25][:steps] + [41, 42, 43, 44][:steps]
                + list(range(17, 29)))
    q = _counters(eng, "jetstream:dsa_query_tokens_total", "form")
    r = _counters(eng, "jetstream:dsa_rows_total", "kind")
    assert q == {"selected": sum(c > TOPK for c in contexts),
                 "all": sum(c <= TOPK for c in contexts)}
    assert r == {"scored": sum(contexts),
                 "attended": sum(min(c, TOPK) for c in contexts)}
