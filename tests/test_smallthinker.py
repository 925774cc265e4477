"""Window and full K/V attention in one model (models/llama.py with
``kv_window``: SmallThinker's language model) at a small size: 7 query heads
a KV head, a window of 11 tokens over pages of 4, two periods of a full layer
without a position code and three window layers that rotate, a router that
reads the attention's input and ReGLU experts. The block against the plain
reference, prefill windows and decode through BOTH pool pairs with pages given
back and handed out again (poisoned while they are nobody's), what each of
window, rotation, the router's input and the activation does to the logits,
the window decode kernel against its plain form, the owner of both kinds of
cache layer, the counters from positions, the engine end to end, and what
``convert_hf`` refuses."""

import asyncio
import dataclasses
import functools
import importlib.util
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import latent_table_cases as table_cases
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.blocks import (
    OutOfBlocks, WindowedAllocator, allocator_for)
from llm_d_inference_scheduler_tpu.kvcache import pages, state
from llm_d_inference_scheduler_tpu.models import bind, configs, family, llama
from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
from llm_d_inference_scheduler_tpu.ops import attention as plain_ops
from llm_d_inference_scheduler_tpu.ops import pallas_paged_attention as paged
from llm_d_inference_scheduler_tpu.ops.pallas_latent_attention import (
    run_pages, table_runs)

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = dataclasses.replace(configs.get_config("tiny-swa-kv"), dtype="float32")
WINDOW, BLOCK = CFG.kv_window, CFG.kv_block_size
# float32 on both sides, different summation order (test_reference.py's).
TOL = dict(rtol=2e-4, atol=2e-4)
N = 45                       # tokens of the sequence the tests follow
RUN = pages.RUN_PAGES        # pages a stretch of the window pool


def _reference():
    path = REPO / "chipbench" / "configs" / "reference_smallthinker.py"
    spec = importlib.util.spec_from_file_location("reference_smallthinker",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(cfg):
    window = [int(ch == "W") for ch in cfg.layer_pattern]
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                norm_eps=cfg.norm_eps,
                experts_per_token=cfg.experts_per_token,
                rope_layout=[w or int(not cfg.full_nope) for w in window],
                sliding_window_layout=window,
                sliding_window_size=cfg.kv_window, q_block=16)


def _init(cfg, seed):
    return jax.jit(functools.partial(llama.init_params, cfg))(
        jax.random.key(seed))


def _plain(cfg, params, seq):
    """The plain reference's logits (jitted: its loops are Python's)."""
    ref = _reference()
    return np.asarray(jax.jit(
        lambda p, t: ref.forward(p, t, **_sizes(cfg)))(params, seq))


@functools.lru_cache(maxsize=None)
def _fixture():
    params = _init(CFG, 3)
    seq = np.asarray(jax.random.randint(jax.random.key(5), (N,), 0,
                                        CFG.vocab_size))
    return params, seq, _plain(CFG, params, seq)


# ---------- the block against the plain reference ----------

def test_family_geometry_and_what_names_the_kinds():
    assert family(CFG) is llama and CFG.layer_pattern == "*WWW*WWW"
    assert not CFG.mixer_pattern and not CFG.tallies_choices
    assert (CFG.n_kv_layers, CFG.n_window_layers, CFG.n_expert_layers,
            CFG.window) == (2, 6, 8, 11)
    assert CFG.n_heads // CFG.n_kv_heads == 7
    window, among = llama._kinds(CFG)
    assert list(window) == [False, True, True, True] * 2
    assert list(among) == [0, 0, 1, 2, 1, 3, 4, 5]
    # The weights stay one stack; a model without the flag has no kinds.
    params = jax.eval_shape(functools.partial(llama.init_params, CFG),
                            jax.random.key(0))
    assert params["layers"]["wq"].shape == (8, 64, 14 * 16)
    assert params["layers"]["w1"].shape == (8, 8, 64, 48)
    assert llama._kinds(configs.get_config("tiny-moe")) is None


def test_forward_matches_the_plain_reference():
    """Contexts past the window (11), both kinds of layer twice over."""
    params, seq, want = _fixture()
    got, _ = jax.jit(functools.partial(llama.forward, cfg=CFG))(
        params, tokens=jnp.asarray(seq)[None])
    np.testing.assert_allclose(np.asarray(got[0]), want, **TOL)


def test_grouped_experts_take_the_early_routers_choices():
    """The grouped matmul's ReGLU (interpreter; widths of whole lanes) under
    the choices of a router that read the attention's input: the
    dense-over-experts form's result, and the reference's."""
    cfg = dataclasses.replace(CFG, d_model=128, d_ff=128, n_layers=2,
                              layer_pattern="*W")
    params = _init(cfg, 4)
    seq = np.asarray(jax.random.randint(jax.random.key(6), (24,), 0, 512))
    want = _plain(cfg, params, seq)
    for impl in ("dense", "grouped_interpret"):
        got, _ = jax.jit(functools.partial(
            llama.forward, cfg=dataclasses.replace(cfg, moe_impl=impl)))(
                params, tokens=jnp.asarray(seq)[None])
        np.testing.assert_allclose(np.asarray(got[0]), want, **TOL)


def _poison(cache, owner):
    """The window pools' pages that are nobody's, overwritten: a step that
    read one would show it."""
    free = np.asarray(owner.free_window_pages(), np.int32)
    return dataclasses.replace(cache, win=cache.win.at[:, free].set(1e4),
                               win_v=cache.win_v.at[:, free].set(1e4))


@pytest.mark.parametrize("kernels", [False, True])
def test_windows_then_decode_through_both_pools(kernels):
    """The prompt in windows of 8 tokens, then decode a token at a time,
    both pool pairs under the owner's tables: every window's last logits and
    every step's are the reference's: across the window's edge (11 tokens),
    over pages that were given back, handed out again and poisoned in
    between."""
    params, seq, want = _fixture()
    mcfg = bind(CFG, platform="cpu", interpret=kernels).mcfg
    assert mcfg.swa_impl == ("kernel_interpret" if kernels else "xla")
    geom = pages.PageGeometry.for_engine(mcfg, 2, 64)
    owner = allocator_for(geom, True)
    cache, _ = pages.alloc(geom)
    assert cache.k.shape == cache.v.shape == geom.shape == (2, 33, 4, 2, 16)
    assert cache.win.shape == cache.win_v.shape == geom.window.shape \
        == (6, 81, 4, 2, 16)      # (4 lanes + 1) x 2 stretches of 8 pages
    per, prompt, win = geom.max_blocks_per_seq, 29, 8
    table = owner.alloc(per)
    row = np.zeros((1, per), np.int32)
    row[0, :len(table)] = table
    attend = functools.partial(pages.decode_attention, kernel=kernels,
                               interpret=kernels)

    @jax.jit
    def first(tokens, n, cache, row):
        logits, (fresh, _) = llama.forward(params, mcfg, tokens, want_kv=True)
        return logits[0, n[0] - 1], pages.write_sequences(
            cache, None, fresh, None, row, n)[0]

    @jax.jit
    def later(tokens, n, written, cache, row):
        logits, cache, _ = llama.prefill_with_prefix(
            params, mcfg, tokens, n, written, cache, None, row)
        return logits[0], cache

    @jax.jit
    def decode(tokens, positions, cache, tables):
        logits, cache, _ = llama.decode_step(
            params, mcfg, tokens, positions, cache, None, tables,
            attention_fn=attend)
        return logits[0], cache

    handed, most = [], 0
    for lo in range(0, prompt, win):
        m = min(win, prompt - lo)
        # (Poisoned BEFORE the owner slides: what a prefill window gives
        # back ahead of its dispatch it still reads.)
        cache = _poison(cache, owner)
        wt = np.zeros((1, per), np.int32)
        owner.slide(table, lo, lo + m, wt[0], True)
        handed += [b for b in table.window if b]
        toks = np.zeros((1, win), np.int32)
        toks[0, :m] = seq[lo:lo + m]
        held = state.at_slots(cache, [0], wt)
        if lo == 0:
            last, cache = first(toks, jnp.asarray([m]), held, row)
        else:
            last, cache = later(toks, jnp.asarray([m]), jnp.asarray([lo]),
                                held, row)
        cache, counts, *_ = state.take_counts(cache)
        assert counts is None          # every choice is an expert held here
        np.testing.assert_allclose(np.asarray(last), want[lo + m - 1], **TOL)
    tables = np.zeros((2, per), np.int32)
    tables[0] = row[0]
    for t in range(prompt, N):
        wt = np.zeros((2, per), np.int32)
        owner.slide(table, t, t + 1, wt[0])
        handed += [b for b in table.window if b]
        most = max(most, sum(b > 0 for b in table.window))
        logits, cache = decode(
            jnp.asarray([seq[t], 0]), jnp.asarray([t, 0]),
            state.at_slots(_poison(cache, owner), [0, 2], wt), tables)
        cache, *_ = state.take_counts(cache)
        np.testing.assert_allclose(np.asarray(logits), want[t], **TOL)
    # The first stretch came back (and was poisoned) while the lane decoded
    # on, and the lane never held more than its reservation.
    assert table.first == RUN and len(set(handed)) == 2 * RUN
    assert most <= geom.window.lane_stretches * RUN
    owner.free(table)
    assert owner.stretches.free_blocks == owner.stretches.n_blocks - 1
    assert owner.tables == 0


@pytest.mark.parametrize("what, change", [
    ("a window of 10", dict(kv_window=WINDOW - 1)),
    ("a window of 12", dict(kv_window=WINDOW + 1)),
    ("rotary on the full layers", dict(full_nope=False)),
    ("the router fed the FFN's input", dict(router_input="ffn")),
    ("SwiGLU experts", dict(expert_act="swiglu")),
])
def test_each_of_window_rotary_router_input_and_reglu_shows_in_the_logits(
        what, change):
    """The same weights read by a configuration without one of them: the
    logits part from the reference's by far more than rounding."""
    params, seq, want = _fixture()
    other = dataclasses.replace(CFG, **change)
    got, _ = jax.jit(functools.partial(llama.forward, cfg=other))(
        params, tokens=jnp.asarray(seq)[None])
    # (Positions inside the window can agree; the later ones cannot.)
    assert np.abs(np.asarray(got[0]) - want)[WINDOW + 1:].max() > 5e-3, what


def test_long_windows_take_their_queries_a_block_at_a_time():
    """banded_attention's loop over query blocks against the whole product."""
    keys = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(keys[0], (1, 64, 14, 16), jnp.float32)
    k = jax.random.normal(keys[1], (1, 96, 2, 16), jnp.float32)
    v = jax.random.normal(keys[2], (1, 96, 2, 16), jnp.float32)
    kw = dict(q_positions=jnp.arange(32, 96)[None],
              kv_positions=jnp.arange(96)[None],
              kv_valid=(jnp.arange(96) != 40)[None])
    for window in (None, 11, jnp.int32(11)):
        whole = plain_ops.banded_attention(q, k, v, **kw, window=window)
        for q_block in (16, 24):       # 64 queries: whole blocks, and not
            blocks = plain_ops.banded_attention(q, k, v, **kw, window=window,
                                                q_block=q_block)
            np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                                       **TOL)
    np.testing.assert_allclose(
        np.asarray(plain_ops.banded_attention(q, k, v, **kw)),
        np.asarray(plain_ops.causal_attention(q, k, v, **kw)), **TOL)


# ---------- the window decode kernel against its plain form ----------

@pytest.mark.parametrize("positions", [
    [0, 1], [9, 10], [11, 12], [10, 21], [23, 15], [39, 3]])
def test_window_decode_kernel_matches_the_plain_form(positions):
    """Positions at 0, inside the first window, at its edge (11), at a
    page's edge, across pages: the kernel's walk from the window's first page
    against the gather, 14 query heads on 2 KV heads, on pools whose pages
    before the window hold what a stale read would show."""
    H, Hkv, D = 14, 2, 16
    keys = jax.random.split(jax.random.key(11), 5)
    k_pool = jax.random.normal(keys[0], (2, 24, BLOCK, Hkv, D), jnp.float32)
    v_pool = jax.random.normal(keys[1], (2, 24, BLOCK, Hkv, D), jnp.float32)
    tables = jnp.asarray([[3, 9, 4, 11, 5, 6, 7, 8, 10, 12],
                          [13, 2, 14, 1, 15, 16, 17, 18, 19, 20]], jnp.int32)
    t = jnp.asarray(positions, jnp.int32)
    # Pages wholly before a lane's window are given back: another's now.
    first = np.maximum(np.asarray(positions) - (WINDOW - 1), 0) // BLOCK
    stale = np.asarray(tables).copy()
    for lane, n in enumerate(first):
        stale[lane, :n] = 23
    k_pool, v_pool = k_pool.at[:, 23].set(1e4), v_pool.at[:, 23].set(1e4)
    q = jax.random.normal(keys[2], (2, H, D), jnp.float32)
    cur_k = jax.random.normal(keys[3], (2, Hkv, D), jnp.float32)
    cur_v = jax.random.normal(keys[4], (2, Hkv, D), jnp.float32)
    args = (q, k_pool, v_pool, jnp.int32(1), jnp.asarray(stale), t + 1,
            cur_k, cur_v)
    want = plain_ops.swa_paged_decode_attention(*args, window=WINDOW)
    got = paged.swa_paged_decode_attention_kernel(*args, window=WINDOW,
                                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    # By hand: the softmax over the lane's last WINDOW rows, its own last.
    for lane, pos in enumerate(positions):
        def rows(pool, cur):
            cached = np.asarray(pool[1, tables[lane]]).reshape(-1, Hkv, D)
            return np.concatenate([cached[:pos],
                                   np.asarray(cur[lane])[None]])[-WINDOW:]

        ks, vs = rows(k_pool, cur_k), rows(v_pool, cur_v)
        for h in range(H):
            s = ks[:, h // 7] @ np.asarray(q[lane, h]) / D ** 0.5
            p = np.exp(s - s.max())
            np.testing.assert_allclose(np.asarray(want[lane, h]),
                                       (p / p.sum()) @ vs[:, h // 7], **TOL)


@pytest.mark.parametrize("n_kv", [4, 8])
@pytest.mark.parametrize("case", table_cases.CASES)
def test_window_kernel_fetches_runs_of_adjacent_pages_as_one_copy(
        case, n_kv, monkeypatch):
    """The window walk over every kind of table (tests/latent_table_cases.py
    read as tables by logical page, a window of 200 rows: the cut is 21
    entries from an aligned one, two stages), 7 query heads a KV head: the
    gather's result (whose cut starts at the window's own first page), and
    the same to the last bit with the same rows at shuffled pages, where no
    group is a run."""
    c, D, window = table_cases, 32, 200
    tables, lens = c.tables(case)
    cut, cut_lens, skip = plain_ops.window_table(
        jnp.asarray(tables), jnp.asarray(lens), c.BLOCK, window,
        align=pages.RUN_PAGES)
    assert cut.shape[1] == plain_ops.window_pages(c.BLOCK, window, 8) == 21
    assert c.shrink_kv_stage(monkeypatch, paged, n_kv, D, cut.shape[1]) == c.STAGE
    pools = [jnp.asarray(pool) for pool in
             c.kv_pools_under(tables, lens, n_kv, D, seed=41)]
    keys = jax.random.split(jax.random.key(41), 3)
    q = jax.random.normal(keys[0], (len(lens), 7 * n_kv, D), jnp.float32)
    cur_k, cur_v = (jax.random.normal(k, (len(lens), n_kv, D), jnp.float32)
                    for k in keys[1:])
    seq_lens = jnp.maximum(jnp.asarray(lens), 1)
    want = plain_ops.swa_paged_decode_attention(
        q, *pools, 1, jnp.asarray(tables), seq_lens, cur_k, cur_v,
        window=window)
    got = paged.swa_paged_decode_attention_kernel(
        q, *pools, 1, jnp.asarray(tables), seq_lens, cur_k, cur_v,
        window=window, interpret=True)
    assert np.abs(np.asarray(want)).max() < 10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    far_tables, far_pools = c.moved(tables, pools, seed=4)
    far_cut = plain_ops.window_table(
        jnp.asarray(far_tables), seq_lens, c.BLOCK, window,
        align=pages.RUN_PAGES)
    assert not np.asarray(table_runs(far_cut[0], far_cut[1], c.BLOCK, 8)).any()
    np.testing.assert_array_equal(
        np.asarray(paged.swa_paged_decode_attention_kernel(
            q, *far_pools, 1, jnp.asarray(far_tables), seq_lens, cur_k,
            cur_v, window=window, interpret=True)), np.asarray(got))


@pytest.mark.parametrize("n_kv", [4, 8])
def test_window_kernel_walks_the_pools_stretches_from_every_offset(
        n_kv, monkeypatch):
    """Tables as the window pool's owner fills them (aligned stretches of 8
    pages, each anywhere in the pool; what lies behind the lane's first
    stretch given back and another's): the window's first page at every
    offset into its stretch, a lane shorter than the window, a lane in its
    first page and one with nothing cached. Every whole group of the cut
    table is a run, and the result is the gather's."""
    c, D, window, R = table_cases, 32, 200, pages.RUN_PAGES
    seq_lens = np.asarray(
        [window + c.BLOCK * (8 + off) + 5 + off for off in range(R)]
        + [window + c.BLOCK * 11, 60, 3, 1], np.int32)
    B = len(seq_lens)
    rng = np.random.default_rng(9)
    stretch = rng.permutation((c.N_BLOCKS - 1) // R)[:B * 4]
    owned = np.zeros((B, c.WIDTH), np.int32)     # four stretches a lane
    owned[:, :4 * R] = (1 + R * stretch[:, None] + np.arange(R)).reshape(B, -1)
    pools = [jnp.asarray(pool) for pool in
             c.kv_pools_under(owned, seq_lens, n_kv, D, seed=43)]
    tables = owned.copy()
    for lane, n in enumerate(seq_lens):      # the stretches out of reach
        first = max(n - window, 0) // c.BLOCK
        tables[lane, :first - first % R] = c.N_BLOCKS - 1
    pools = [pool.at[:, c.N_BLOCKS - 1].set(1e4) for pool in pools]
    cut, cut_lens, skip = plain_ops.window_table(
        jnp.asarray(tables), jnp.asarray(seq_lens), c.BLOCK, window, align=R)
    assert c.shrink_kv_stage(monkeypatch, paged, n_kv, D, cut.shape[1]) == c.STAGE
    assert sorted(set((np.asarray(skip[:R]) // c.BLOCK).tolist())) \
        == list(range(R))
    runs = np.asarray(table_runs(cut, cut_lens, c.BLOCK, run_pages(c.STAGE)))
    whole = -(-(np.asarray(cut_lens) - 1) // c.BLOCK) // R
    assert runs.sum(axis=1).tolist() == whole.tolist() and whole[:R].all()
    keys = jax.random.split(jax.random.key(43), 3)
    q = jax.random.normal(keys[0], (B, 7 * n_kv, D), jnp.float32)
    cur_k, cur_v = (jax.random.normal(k, (B, n_kv, D), jnp.float32)
                    for k in keys[1:])
    args = (q, *pools, 1, jnp.asarray(tables), jnp.asarray(seq_lens), cur_k,
            cur_v)
    want = plain_ops.swa_paged_decode_attention(*args, window=window)
    got = paged.swa_paged_decode_attention_kernel(*args, window=window,
                                                  interpret=True)
    assert np.abs(np.asarray(want)).max() < 10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_the_full_layers_kernel_reads_seven_heads_a_group():
    """paged_decode_attention_pallas at 14 query heads on 2 (no multiple of
    8 a group) against the gather."""
    H, Hkv, D = 14, 2, 16
    keys = jax.random.split(jax.random.key(12), 5)
    k_pool = jax.random.normal(keys[0], (2, 9, BLOCK, Hkv, D), jnp.float32)
    v_pool = jax.random.normal(keys[1], (2, 9, BLOCK, Hkv, D), jnp.float32)
    args = (jax.random.normal(keys[2], (2, H, D), jnp.float32), k_pool,
            v_pool, jnp.int32(1),
            jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4),
            jnp.asarray([14, 5], jnp.int32),
            jax.random.normal(keys[3], (2, Hkv, D), jnp.float32),
            jax.random.normal(keys[4], (2, Hkv, D), jnp.float32))
    np.testing.assert_allclose(
        np.asarray(paged.paged_decode_attention_pallas(*args,
                                                       interpret=True)),
        np.asarray(plain_ops.paged_decode_attention(
            *args[:6], cur_k=args[6], cur_v=args[7])), **TOL)


# ---------- the owner of both kinds of cache layer ----------

def test_pool_bytes_follow_the_lanes_and_not_the_context():
    short = pages.PageGeometry.for_engine(CFG, 4, 64)
    long = pages.PageGeometry.for_engine(CFG, 4, 4096)
    wide = pages.PageGeometry.for_engine(CFG, 8, 64)
    assert long.pool_bytes > 50 * short.pool_bytes
    assert long.window == short.window
    assert short.window.lane_pages == 4             # ceil(11 / 4) + 1
    assert short.window.lane_stretches == 2         # ceil(4 / 8) + 1
    assert short.window.lanes == 4 + 2
    assert short.window.n_blocks == 1 + (6 + 1) * 2 * RUN
    assert wide.window.n_blocks == 1 + (10 + 1) * 2 * RUN
    got = short.describe()
    assert (got["kv_layers"], got["kv_layers_full"], got["kv_layers_window"],
            got["window"]) == (2, 2, 6, 11)
    assert got["window_token_bytes"] == got["kv_token_bytes"] == 2 * 2 * 16 * 4
    assert got["window_pool_bytes"] == 6 * 113 * 4 * 256
    assert any("prefix hits" in s for s in got["off_for_window_layers"])
    assert short.one_chip_only.startswith("K/V page pools and a second pair")
    assert type(allocator_for(short, True)) is WindowedAllocator
    # At the cell's widths (chipbench/configs/smallthinker-21b-a3b-cut.json):
    # 257 pages a lane in 34 stretches of 8, 2,048 B a token a window layer,
    # whatever the context.
    cell = dataclasses.replace(
        CFG, kv_block_size=16, kv_window=4096, n_heads=28, n_kv_heads=4,
        head_dim_override=128, dtype="bfloat16")
    geom = pages.PageGeometry.for_engine(cell, 32, 16384)
    assert geom.window.lane_pages == 257 and geom.window.lanes == 36
    assert (geom.run_pages, geom.window.lane_stretches) == (8, 34)
    assert geom.window.token_bytes == geom.token_bytes == 2048
    assert geom.window.n_blocks == 1 + 37 * 34 * 8 == 10065
    assert geom.window.pool_bytes == 6 * 10065 * 16 * 2048
    assert geom.pool_bytes == 2 * (1 + 32 * 1024) * 16 * 2048
    assert pages.PageGeometry.for_engine(cell, 32, 8192).window == geom.window


def test_a_lane_holds_the_windows_pages_and_admission_reserves_by_kind():
    geom = pages.PageGeometry.for_engine(CFG, 2, 512)
    owner = allocator_for(geom, True)
    per, w = geom.max_blocks_per_seq, geom.window
    assert RUN * (owner.stretches.n_blocks - 1) == w.n_blocks - 1
    assert owner.lanes == 4 and owner.run == RUN
    tables = [owner.alloc(n) for n in (per, 3, 1, 9)]
    assert owner.free_blocks == 0 and owner.tables == 4
    with pytest.raises(OutOfBlocks, match="reservation"):
        owner.alloc(1)
    owner.free(tables.pop())
    # A prompt of 200 in windows of 32, then 40 decode chunks of 4 steps:
    # the lane holds whole aligned stretches, those that hold a page in reach
    # and no other, and never more than its reservation.
    table, seen = tables[0], set()

    def stretches(first_page, last_page):
        return list(range(first_page // RUN, last_page // RUN + 1))

    def held_stretches():
        assert table.first % RUN == 0 and len(table.window) % RUN == 0
        groups = [table.window[i:i + RUN]
                  for i in range(0, len(table.window), RUN)]
        assert all(g == list(range(g[0], g[0] + RUN)) and g[0] % RUN == 1
                   for g in groups)
        seen.update(table.window)
        return [table.first // RUN + i for i in range(len(groups))]

    for lo in range(0, 200, 32):
        hi = min(lo + 32, 200)
        row = np.zeros(per, np.int32)
        owner.slide(table, lo, hi, row, True)
        # What only this window read is back already.
        assert held_stretches() == stretches(
            max(hi - (WINDOW - 1), 0) // BLOCK, (hi - 1) // BLOCK)
        assert len(table.window) <= w.lane_stretches * RUN
    pos = 200
    for _ in range(40):
        row = np.zeros(per, np.int32)
        owner.slide(table, pos, pos + 4, row)
        first, last = (pos - (WINDOW - 1)) // BLOCK, (pos + 3) // BLOCK
        assert held_stretches() == stretches(first, last)
        assert len(table.window) <= w.lane_stretches * RUN
        lo, hi = table.first, table.first + len(table.window)
        assert list(row[lo:hi]) == table.window
        assert not row[:lo].any() and not row[hi:].any()
        pos += 4
    assert len(seen) <= w.n_blocks - 1
    for t in tables:
        owner.free(t)
    assert owner.tables == 0
    assert owner.stretches.free_blocks == owner.stretches.n_blocks - 1
    assert owner.free_blocks == owner.n_blocks - 1


@pytest.mark.parametrize("window, lanes, max_len, floor", [
    (4096, 32, 16384, 0.95),     # longctx-16k: 257 pages in reach
    (513, 64, 18432, 0.70),      # longctx-wide: 33, of which a group is 8
], ids=["smallthinker", "dots3"])
def test_the_window_tables_hold_runs_under_the_long_context_cells_churn(
        window, lanes, max_len, floor):
    """Why the window pool is handed out in stretches: the owner under the
    two window cells' shapes (scripts/microbench_decode.window_churn: prompts
    log-uniform 4,096-12,288 written in windows of 1,024 ahead of the decode
    chunks, outputs 512-1,536, chunks of 8 steps). Every whole group of a
    decoding lane's table, cut as the kernels cut it, is a run, which is 97%
    of the groups at 257 pages a lane (a page at a time off the shared LIFO
    list it was 22% after 250 chunks and 0 after 2,000); no page is two
    lanes' at once, no lane holds more than its reservation, the pool never
    runs dry, and every stretch comes back."""
    sys.path.insert(0, str(REPO / "scripts"))
    from microbench_decode import window_churn

    from llm_d_inference_scheduler_tpu.engine.blocks import window_table_groups

    cell = dataclasses.replace(
        CFG, kv_block_size=16, kv_window=window, n_heads=28, n_kv_heads=4,
        head_dim_override=128, dtype="bfloat16")
    geom = pages.PageGeometry.for_engine(cell, lanes, max_len)
    w, runs, splits, most = geom.window, 0, 0, 0
    for owner, decoded in window_churn(geom, lanes, chunks=250, seed=2):
        held = [page for table, _, _ in decoded for page in table.window
                if page]
        assert len(held) == len(set(held))
        for table, row, pos in decoded:
            assert len(table.window) <= w.lane_stretches * RUN
            r, s = window_table_groups(row, pos, 16, window, RUN)
            first = max(pos + 1 - window, 0) // 16 // RUN * RUN
            assert r == (-(-pos // 16) - first) // RUN     # every whole one
            runs, splits = runs + r, splits + s
        most = max(most, owner.stretches.n_blocks - 1
                   - owner.stretches.free_blocks)
    assert runs / (runs + splits) >= floor and runs > 10_000
    assert most <= lanes * w.lane_stretches + w.lane_stretches
    assert owner.tables == 0
    assert owner.stretches.free_blocks == owner.stretches.n_blocks - 1
    assert owner.free_blocks == owner.n_blocks - 1


# ---------- the counters ----------

def _counters(telemetry, name, label):
    return {s.labels[label]: s.value
            for m in telemetry.registry.collect() for s in m.samples
            if s.name == name}


def test_window_and_expert_counters_from_positions():
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
    from llm_d_inference_scheduler_tpu.engine.telemetry import EngineTelemetry

    eng = object.__new__(TpuEngine)
    eng.cfg = EngineConfig(model="tiny-swa-kv", max_batch=4, decode_chunk=4)
    eng.bound = bind(CFG, platform="cpu")
    eng.telemetry = EngineTelemetry(block_size=4, num_blocks=8)
    for op, args in (
            (("decode",), dict(
                positions=np.asarray([2, 40, 0, 0], np.int32),
                slots=np.asarray([0, 2, 4, 4], np.int32), steps=3)),
            (("prefix_prefill", 16, 2), dict(
                tokens=np.zeros((1, 16), np.int32),
                slots=np.asarray([1], np.int32),
                prefix_len=np.asarray([4], np.int32),
                suffix_len=np.asarray([6], np.int32)))):
        real, queries = eng._requests_part(op, args)
        eng.telemetry.book_program(eng.bound.program_counts(
            op[0], args["slots" if op[0] == "decode" else "tokens"].size,
            args.get("steps", 1), real=real, queries=queries))
    contexts = [3, 4, 5, 41, 42, 43] + list(range(5, 11))
    assert _counters(eng.telemetry, "jetstream:swa_rows_total", "kind") == {
        "context": sum(contexts),
        "attended": sum(min(c, WINDOW) for c in contexts)}
    assert _counters(eng.telemetry, "jetstream:moe_ffn_tokens_total",
                     "form") == {"dense": 4 * 3 + 16}
    # No latent family's counter is booked for it, and a K/V model without
    # window layers books no window's.
    assert not _counters(eng.telemetry, "jetstream:mla_attention_tokens_total",
                         "form")
    plain = bind(configs.get_config("tiny-moe"), platform="cpu")
    assert not any(n == "swa_rows" for n, _, _ in plain.program_counts(
        "decode", 4, 2, real=1, queries=(np.asarray([5]), np.asarray([2]))))


def test_bind_resolves_the_window_walk_by_the_latent_familys_rule():
    assert bind(CFG, platform="cpu").mcfg.swa_impl == "xla"
    assert bind(CFG, platform="cpu", interpret=True).mcfg.swa_impl \
        == "kernel_interpret"
    # On a TPU the kernel, where a page's DMA is whole lanes (head_dim 128).
    wide = dataclasses.replace(CFG, head_dim_override=128)
    assert bind(wide, platform="tpu").mcfg.swa_impl == "kernel"
    assert bind(CFG, platform="tpu").mcfg.swa_impl == "xla"
    said = bind(CFG, platform="cpu").describe()
    assert (said["window_attention"], said["router_input"],
            said["expert_activation"]) == ("xla", "attn", "reglu")
    other = bind(configs.get_config("tiny-moe"), platform="cpu").describe()
    assert not {"window_attention", "router_input", "expert_activation"} \
        & set(other)


# ---------- a continuation window as one kernel over the pages ----------

def _cell_model():
    """``smallthinker-21b-a3b-cut`` as the benchmark's file states it."""
    import json

    with open(REPO / "chipbench" / "configs"
              / "smallthinker-21b-a3b-cut.json") as f:
        return config_from_hf(types.SimpleNamespace(**json.load(f)),
                              name="smallthinker-21b-a3b-cut")


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the programs under its scans,
    conds and calls; a kernel's own body is the kernel's."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("form, kernels, gathers", [("kernel", 1, 0),
                                                    ("xla", 0, 4)])
def test_the_cells_continuation_program_walks_the_pools_in_the_kernel(
        form, kernels, gathers):
    """The continuation program of the benchmark's configuration (1,024
    queries behind a prior table of 256 blocks, traced on shapes alone):
    bound for a TPU it holds the kernel's call ONCE, for the layers of both
    kinds (its text is set-up time), handed both kinds' stacked pools whole
    and the sequence's whole table, and NO gather reads a pool; the plain
    form gathers K and V of each kind in a ``cond`` branch each, and calls no
    kernel."""
    bound = bind(_cell_model(), platform="tpu")
    assert bound.mcfg.swa_impl == "kernel"
    mcfg = dataclasses.replace(bound.mcfg, swa_impl=form)
    geom = pages.PageGeometry.for_engine(mcfg, 32, 16384)
    shapes = (geom.shape, geom.window.shape)
    assert shapes == ((2, 32769, 16, 4, 128), (6, 10065, 16, 4, 128))
    params = jax.eval_shape(lambda k: llama.init_params(mcfg, k),
                            jax.random.key(0))
    cache = state.at_slots(
        jax.eval_shape(lambda: pages.alloc(geom)[0]), [0],
        np.zeros((1, geom.max_blocks_per_seq), np.int32))
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    traced = jax.make_jaxpr(
        lambda *a: llama.prefill_with_prefix(a[0], mcfg, *a[1:]))(
        params, jax.ShapeDtypeStruct((1, 1024), jnp.int32), one, one, cache,
        None, jax.ShapeDtypeStruct((1, geom.max_blocks_per_seq), jnp.int32),
        jax.ShapeDtypeStruct((1, 256), jnp.int32))
    calls, reads = [], []
    for eqn in _eqns(traced.jaxpr):
        of_pools = [v.aval.shape for v in eqn.invars
                    if getattr(v.aval, "shape", None) in shapes]
        if eqn.primitive.name == "pallas_call":
            calls.append((str(eqn.params["name"]), of_pools,
                          [v.aval.shape for v in eqn.invars[:2]]))
        elif eqn.primitive.name == "gather" and of_pools:
            reads.append(of_pools)
    assert len(reads) == gathers
    walks = [c for c in calls if "kv_window_prefill_attention" in c[0]]
    assert len(walks) == kernels
    for _, of_pools, (tables, runs) in walks:
        assert of_pools == [shapes[0]] * 2 + [shapes[1]] * 2
        # The sequence's 1,024 entries and the 256 pages a band of 4,096
        # reaches, a flag a group of 8: nothing of the prior bucket.
        assert (tables, runs) == ((1024 + 256,), ((1024 + 256) // 8,))


def test_the_continuation_microbenchmark_rehearses_on_the_cpu(capsys,
                                                              monkeypatch):
    """scripts/microbench_decode.py --kv-prefill at the cell's widths and a
    small window, the kernel interpreted: a line a (layer kind, prior
    bucket, form), the kernel within bf16's rounding of the plain form."""
    import json

    spec = importlib.util.spec_from_file_location(
        "microbench_decode", REPO / "scripts" / "microbench_decode.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr("llm_d_inference_scheduler_tpu.utils.compile_cache."
                        "configure_compile_cache", lambda: "")
    bench.main(["--kv-prefill", "--kv-prefill-interpret",
                "--kv-prefill-tokens", "32", "--kv-prefill-lanes", "1",
                "--max-model-len", "1024", "--kv-prefill-priors", "8,40",
                "--kv-prefill-iters", "1", "--kv-prefill-query-tiles", "16"])
    assert paged.QUERY_TILE == 256         # the script put it back
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [(ln["layer"], ln["prior_blocks"], ln["form"]) for ln in lines] \
        == [(kind, prior, form) for kind in ("full", "window")
            for prior in (8, 40) for form in ("xla", "kernel")]
    for ln in lines:
        assert ln["component"] == "kv_window_prefill_attention"
        assert (ln["heads"], ln["window"]) == (28, 32)
        assert ln["live_rows"] == ln["prior_blocks"] * 12
        # A full layer's table is the prior bucket, or for the kernel the
        # sequence's whole table (1,024 tokens in pages of 16); a window
        # layer's the pages its band of 4,096 reaches.
        assert ln["table_width"] == (
            256 if ln["layer"] == "window" else
            ln["prior_blocks"] if ln["form"] == "xla" else 64)
        assert ln["mxu_peak_share_pct"] is None      # no chip: no share
        if ln["form"] == "kernel":
            assert ln["query_tile"] == 16 and ln["max_err_vs_plain"] < 0.05


# ---------- the engine, end to end ----------

@pytest.fixture
def served():
    """The model in float32 under a name an engine can be asked for."""
    name = "tiny-swa-kv-f32"
    configs._REGISTRY[name] = dataclasses.replace(CFG, name=name)
    yield name
    del configs._REGISTRY[name]


def test_engine_serves_through_windows_and_both_kinds_of_pool(served):
    """Three prompts on two lanes (the third waits for a lane and takes the
    pages the first two gave back), written in windows of 8, decoded in
    chunks of 4 past the window: greedy tokens are the plain forward's; the
    window pool's gauge, counters and /health say what the positions do."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    prompts = {"A": [1] + [(j * 17) % 450 + 3 for j in range(37)],
               "B": [1] + [(j * 5) % 450 + 3 for j in range(9)],
               "C": [1] + [(j * 11) % 450 + 3 for j in range(20)]}

    async def serve(cfg):
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            async def one(rid, n):
                out = eng.submit(EngineRequest(
                    request_id=rid, prompt_token_ids=list(prompts[rid]),
                    max_tokens=n, temperature=0.0, ignore_eos=True))
                toks = []
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=300)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                        assert not ev.cached_tokens
                    if ev.finish_reason is not None:
                        return toks

            got = await asyncio.gather(one("A", 14), one("B", 22),
                                       one("C", 9))
            with pytest.raises(ValueError, match="window of the context"):
                eng.submit(EngineRequest(
                    request_id="pd", prompt_token_ids=prompts["B"],
                    kv_transfer_params={"do_remote_decode": True}))
            plain = []
            for rid, toks in zip("ABC", got):
                told = jnp.asarray([prompts[rid] + toks])
                logits = jax.jit(lambda p, t: llama.forward(
                    p, eng.mcfg, t)[0])(eng.params, told)
                plain.append([int(logits[0, len(prompts[rid]) - 1 + i]
                                  .argmax()) for i in range(len(toks))])
            usage = [s.value for m in eng.telemetry.registry.collect()
                     for s in m.samples
                     if s.name == "jetstream:kv_window_cache_usage_perc"]
            return (got, plain, usage, eng.allocator,
                    _counters(eng.telemetry, "jetstream:swa_rows_total",
                              "kind"),
                    _counters(eng.telemetry,
                              "jetstream:moe_ffn_tokens_total", "form"),
                    {name: _counters(eng.telemetry, f"jetstream:{name}_total",
                                     "kind")
                     for name in ("kv_table_groups",
                                  "kv_window_table_groups")},
                    _counters(eng.telemetry,
                              "jetstream:kv_prefill_attention_tokens_total",
                              "form"),
                    eng.describe()["settings"])
        finally:
            await eng.stop()

    (got, plain, usage, owner, rows, ffn, groups, continued,
     settings) = asyncio.run(serve(
        EngineConfig(model=served, backend="tpu", max_batch=2,
                     max_model_len=96, decode_chunk=4, kv_events_port=0,
                     seed=7, prefill_chunk=8, pallas_attention=True,
                     pallas_interpret=True)))
    assert got == plain and [len(t) for t in got] == [14, 22, 9]
    # Every request gave everything back, of both kinds.
    assert usage == [0.0] and owner.tables == 0
    assert owner.stretches.free_blocks == owner.stretches.n_blocks - 1
    assert owner.free_blocks == owner.n_blocks - 1
    assert 0 < rows["attended"] < rows["context"]
    assert ffn["dense"] > 0
    # Every continuation window (4 + 1 + 2 of 8 padded tokens after the
    # prompts' first, and the warm-up's) went through the kernel that walks
    # the pages.
    assert set(continued) == {"kernel"} and continued["kernel"] >= 7 * 8
    assert continued["kernel"] % 8 == 0
    # The three admitted tables (13, 8 and 8 pages, prompt and output) in
    # groups of 8 as the full layers' kernel fetches them, and the window
    # tables of the decoding lanes, once a chunk of 4 steps (a window of 11
    # tokens in pages of 4 seldom fills a group of 8: most are the short
    # last one; a whole one is a stretch, so a run).
    assert settings["kv_run_pages"] == RUN
    assert groups["kv_table_groups"] == {"run": 3.0, "split": 1.0}
    window_groups = groups["kv_window_table_groups"]
    assert 0 < window_groups["run"] < window_groups["split"]
    assert sum(window_groups.values()) >= (14 + 22 + 9) // 4
    assert (settings["kv_layers_full"], settings["kv_layers_window"],
            settings["window"]) == (2, 6, WINDOW)
    assert (settings["window_attention"], settings["router_input"],
            settings["expert_activation"]) == ("kernel_interpret", "attn",
                                               "reglu")
    assert settings["window_pool_bytes"] == 6 * (1 + 5 * 2 * RUN) * 4 * 256
    assert not settings["prefix_caching"]      # asked for or not


@pytest.mark.parametrize("extra", [dict(tp_size=2), dict(role="prefill"),
                                   dict(pp_size=2)])
def test_engine_refuses_what_a_window_pool_cannot_do(extra):
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    with pytest.raises(ValueError, match="window of the context"):
        TpuEngine(EngineConfig(model="tiny-swa-kv", backend="tpu",
                               max_batch=2, max_model_len=64,
                               kv_events_port=0, **extra))


# ---------- what convert_hf maps, and refuses ----------

_PUBLISHED = dict(
    head_dim=16, hidden_size=64, max_position_embeddings=256,
    moe_ffn_hidden_size=48, moe_num_active_primary_experts=3,
    moe_num_primary_experts=8, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, num_attention_heads=14, num_hidden_layers=8,
    num_key_value_heads=2, rms_norm_eps=1e-6,
    rope_layout=[0, 1, 1, 1, 0, 1, 1, 1], rope_scaling=None,
    rope_theta=10_000.0, sliding_window_layout=[0, 1, 1, 1, 0, 1, 1, 1],
    sliding_window_size=11, tie_word_embeddings=False, vocab_size=512)


def test_convert_hf_recognises_the_family_from_its_keys():
    got = config_from_hf(types.SimpleNamespace(**_PUBLISHED), "tiny-swa-kv")
    # (The preset's page of 4 is the tests'; a converted model's is 16.)
    assert got == dataclasses.replace(configs.get_config("tiny-swa-kv"),
                                      kv_block_size=got.kv_block_size)
    # Without window layers the model is one kind of layer, no pattern.
    flat = config_from_hf(types.SimpleNamespace(**{
        **_PUBLISHED, "sliding_window_layout": [0] * 8,
        "rope_layout": [1] * 8}), "flat")
    assert (flat.layer_pattern, flat.kv_window, flat.full_nope,
            flat.router_input, flat.expert_act) == ("", 0, False, "attn",
                                                    "reglu")


@pytest.mark.parametrize("change, said", [
    (dict(moe_primary_router_apply_softmax=False),
     "moe_primary_router_apply_softmax"),
    (dict(rope_scaling={"type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(sliding_window_layout=[0, 1, 1, 1]), "sliding_window_layout"),
    (dict(rope_layout=[0, 1, 1, 1, 0, 1, 1, 2]), "rope_layout"),
    (dict(rope_layout=[1, 0, 1, 1, 1, 0, 1, 1]), "rope_layout"),
    (dict(rope_layout=[0, 1, 1, 1, 1, 1, 1, 1]), "rope_layout"),
    (dict(sliding_window_size=None), "sliding_window_size"),
])
def test_convert_hf_refuses_what_is_not_computed(change, said):
    with pytest.raises(ValueError, match=said):
        config_from_hf(types.SimpleNamespace(**{**_PUBLISHED, **change}),
                       "refused")
