"""Window and full latent attention in one model (models/mla.py with
``window_attn``: ``model_type`` dots3_note) at a small size with widths
aligned to nothing, a window of 7 tokens, 6 selected rows and a page of 4:
the block against the plain reference, prefill windows and decode through
BOTH kinds of pool with pages given back and handed out again (poisoned while
they are nobody's), the chips' shares of an expert layer, the owner of both
kinds of cache layer, what each of gate, rescale, window length and rotary
base does to the logits, the window decode kernel against its plain form, the
counters from positions, and the engine end to end."""

import asyncio
import dataclasses
import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.blocks import (
    BlockAllocator, OutOfBlocks, PrefixCachingAllocator, WindowedAllocator,
    allocator_for)
from llm_d_inference_scheduler_tpu.kvcache import pages, state
from llm_d_inference_scheduler_tpu.models import bind, configs, family, mla
from llm_d_inference_scheduler_tpu.ops import attention as plain_ops
from llm_d_inference_scheduler_tpu.ops import pallas_latent_attention as latent

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = dataclasses.replace(configs.get_config("tiny-swa"), dtype="float32")
WINDOW, TOPK, BLOCK = CFG.window_attn.window, CFG.index_topk, CFG.kv_block_size
# float32 on both sides, different summation order (test_reference.py's).
TOL = dict(rtol=2e-4, atol=2e-4)
N = 45                       # tokens of the sequence the tests follow
RUN = pages.RUN_PAGES        # pages a stretch of the window pool


def _reference():
    path = REPO / "chipbench" / "configs" / "reference_dots3_note.py"
    spec = importlib.util.spec_from_file_location("reference_dots3_note", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(cfg):
    def widths(kind):
        return dict(n_heads=kind.n_heads, kv_lora_rank=kind.kv_lora_rank,
                    qk_nope_head_dim=kind.qk_nope_head_dim,
                    qk_rope_head_dim=kind.qk_rope_head_dim,
                    rope_theta=kind.rope_theta)

    names = {"*": "full_attention", "W": "sliding_attention"}
    return dict(layer_types=[names[ch] for ch in cfg.layer_pattern],
                full=widths(cfg), window=widths(cfg.window_attn),
                sliding_window_size=cfg.window_attn.window,
                norm_eps=cfg.norm_eps, rescale=cfg.mla_scale_q_lora,
                gate=cfg.attn_gate, window_gate=cfg.window_attn.gate,
                experts_per_token=cfg.experts_per_token,
                routed_scaling_factor=cfg.routed_scaling_factor,
                index_n_heads=cfg.index_n_heads,
                index_head_dim=cfg.index_head_dim, index_topk=cfg.index_topk,
                first_expert=cfg.experts_first, q_block=16)


def _share(params, cfg, rank, held):
    """(cfg, params) of the chip that holds experts rank * held .. of every
    expert layer, of both stacks that have them."""
    cut = dict(params)
    for stack in ("layers", "window"):
        cut[stack] = dict(params[stack])
        for name in ("w1", "w2", "w3"):
            cut[stack][name] = params[stack][name][
                :, rank * held:(rank + 1) * held]
    return (dataclasses.replace(cfg, experts_held=held,
                                experts_first=rank * held), cut)


def _init(cfg, seed):
    return jax.jit(functools.partial(mla.init_params, cfg))(
        jax.random.key(seed))


def _plain(cfg, params, seq):
    """The plain reference's logits (jitted: its loops are Python's)."""
    ref = _reference()
    return np.asarray(jax.jit(
        lambda p, t: ref.forward(p, t, **_sizes(cfg)))(params, seq))


@functools.lru_cache(maxsize=None)
def _fixture(held=0, rank=0):
    params = _init(CFG, 3)
    cfg = CFG
    if held:
        cfg, params = _share(params, CFG, rank, held)
    seq = np.asarray(jax.random.randint(jax.random.key(5), (N,), 0,
                                        CFG.vocab_size))
    return cfg, params, seq, _plain(cfg, params, seq)


# ---------- the block against the plain reference ----------

def test_family_geometry_and_what_names_the_kinds():
    assert family(CFG) is mla and CFG.layer_pattern == "**WWW"
    assert (CFG.n_kv_layers, CFG.n_window_layers, CFG.n_expert_layers) == (
        2, 3, 4)
    w = CFG.of_window()
    assert w is CFG.of_window()          # one value, not one a call
    assert (w.n_heads, w.kv_lora_rank, w.latent_dim, w.index_topk) == (
        2, 40, 48, 0)
    shapes = lambda c: jax.eval_shape(  # noqa: E731
        functools.partial(mla.init_params, c), jax.random.key(0))
    params = shapes(CFG)
    assert set(params) == {"embed", "final_norm", "lm_head", "dense",
                           "layers", "window"}
    assert params["layers"]["wg"].shape == (1, 96, 3)
    assert params["window"]["wg"].shape == (3, 96, 2)
    assert params["window"]["wkvb"].shape == (3, 40, 2 * (12 + 16))
    assert "wqb_idx" in params["layers"] and "wqb_idx" not in params["window"]
    assert mla._segments(params, CFG) == [
        ("dense", 0, 1, "full", 0), ("layers", 0, 1, "full", 1),
        ("window", 0, 3, "window", 0)]
    # A pattern of two periods walks the stacks a run at a time.
    two = dataclasses.replace(CFG, n_layers=9, layer_pattern="**WWW*WWW")
    assert mla._segments(shapes(two), two) == [
        ("dense", 0, 1, "full", 0), ("layers", 0, 1, "full", 1),
        ("window", 0, 3, "window", 0), ("layers", 1, 2, "full", 2),
        ("window", 3, 6, "window", 3)]
    # The other latent blocks keep the weights they had (keys of its own).
    plain = configs.get_config("tiny-mla")
    gated = dataclasses.replace(plain, attn_gate=True)
    a, b = _init(plain, 1), _init(gated, 1)
    assert all((a["layers"][k] == b["layers"][k]).all() for k in a["layers"])


@pytest.mark.parametrize("held, rank", [(0, 0), (4, 1)])
def test_forward_matches_the_plain_reference(held, rank):
    """Contexts past the window (7) and past index_topk (6), both kinds of
    layer, both gates, the rescale; a chip's share of the experts."""
    cfg, params, seq, want = _fixture(held, rank)
    got, _ = jax.jit(functools.partial(mla.forward, cfg=cfg))(
        params, tokens=jnp.asarray(seq)[None])
    np.testing.assert_allclose(np.asarray(got[0]), want, **TOL)


def test_two_periods_of_the_pattern_match_the_reference():
    """A stack walked in two runs (a slice of it each), both kinds."""
    cfg = dataclasses.replace(CFG, layer_pattern="**W*W")
    params = _init(cfg, 4)
    seq = np.asarray(jax.random.randint(jax.random.key(6), (20,), 0, 512))
    got, _ = jax.jit(functools.partial(mla.forward, cfg=cfg))(
        params, tokens=jnp.asarray(seq)[None])
    np.testing.assert_allclose(np.asarray(got[0]), _plain(cfg, params, seq),
                               **TOL)


def _poison(cache, owner):
    """The window pool's pages that are nobody's, overwritten: a step that
    read one would show it."""
    free = np.asarray(owner.free_window_pages(), np.int32)
    return dataclasses.replace(cache, win=cache.win.at[:, free].set(1e4))


@pytest.mark.parametrize("kernels", [False, True])
def test_windows_then_decode_through_both_pools(kernels):
    """The prompt in windows of 8 tokens, then decode a token at a time,
    both pools under the owner's tables: every window's last logits and every
    step's are the reference's: across the window's edge (7 tokens), past
    index_topk (6), over pages that were given back, handed out again and
    poisoned in between."""
    cfg, params, seq, want = _fixture()
    mcfg = bind(cfg, platform="cpu", interpret=kernels).mcfg
    assert mcfg.swa_impl == ("kernel_interpret" if kernels else "xla")
    geom = pages.PageGeometry.for_engine(mcfg, 2, 64)
    owner = allocator_for(geom, True)
    cache, _ = pages.alloc(geom)
    assert cache.win.shape == geom.window.shape == (3, 81, 4, 128)
    per, prompt, win = geom.max_blocks_per_seq, 29, 8
    table = owner.alloc(per)
    row = np.zeros((1, per), np.int32)
    row[0, :len(table)] = table
    attend = functools.partial(pages.latent_decode_attention, kernel=kernels,
                               interpret=kernels)

    @jax.jit
    def first(tokens, n, cache, row):
        logits, (fresh, _) = mla.forward(params, mcfg, tokens, want_kv=True)
        return logits[0, n[0] - 1], pages.write_sequences(
            cache, None, fresh, None, row, n)[0]

    @jax.jit
    def later(tokens, n, written, cache, row):
        logits, cache, _ = mla.prefill_with_prefix(
            params, mcfg, tokens, n, written, cache, None, row)
        return logits[0], cache

    @jax.jit
    def decode(tokens, positions, cache, tables):
        logits, cache, _ = mla.decode_step(
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
        cache, *_ = state.take_counts(cache)
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


@pytest.mark.parametrize("stack, layer", [("layers", 0), ("window", 1)])
def test_the_shares_of_all_ranks_and_the_shared_expert_once_are_the_layer(
        stack, layer):
    """Eight chips hold two experts each: their expert layers' outputs, the
    shared expert's taken once, add up to the uncut layer's."""
    cfg, params, _, _ = _fixture()
    h = jax.random.normal(jax.random.key(5), (40, cfg.d_model), jnp.float32)
    of = lambda p: {k: v[layer] for k, v in p[stack].items()}  # noqa: E731
    whole, chose, _ = mla._ffn(cfg, of(params), h)
    shared = mla._swiglu(h, *(of(params)[k] for k in ("w1s", "w3s", "w2s")))
    total, held = 0.0, 0
    for rank in range(8):
        c, p = _share(params, cfg, rank, 2)
        y, again, counts = mla._ffn(c, of(p), h)
        assert (np.asarray(again) == np.asarray(chose)).all()
        total = total + (y - shared)
        held += int(counts[0])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               **TOL)
    assert held == 40 * cfg.experts_per_token


@pytest.mark.parametrize("what, change", [
    ("no gate on the full layers", dict(attn_gate=False)),
    ("no gate on the window layers", dict(window_attn=dataclasses.replace(
        CFG.window_attn, gate=False))),
    ("no rescale", dict(mla_scale_q_lora=False, mla_scale_kv_lora=False)),
    ("a window of 6", dict(window_attn=dataclasses.replace(
        CFG.window_attn, window=WINDOW - 1))),
    ("a window of 8", dict(window_attn=dataclasses.replace(
        CFG.window_attn, window=WINDOW + 1))),
    ("the full layers' rotary base on the window layers", dict(
        window_attn=dataclasses.replace(CFG.window_attn,
                                        rope_theta=CFG.rope_theta))),
    ("the window layers' rotary base on the full layers", dict(
        rope_theta=CFG.window_attn.rope_theta)),
])
def test_each_of_gate_rescale_window_and_rotary_base_shows_in_the_logits(
        what, change):
    """The same weights read by a configuration without one of them: the
    logits part from the reference's by far more than rounding."""
    cfg, params, seq, want = _fixture()
    other = dataclasses.replace(cfg, **change)
    got, _ = jax.jit(functools.partial(mla.forward, cfg=other))(
        params, tokens=jnp.asarray(seq)[None])
    # (Positions inside the window and under index_topk can agree; the
    # later ones cannot.)
    assert np.abs(np.asarray(got[0]) - want)[WINDOW + 1:].max() > 5e-3, what


# ---------- the window decode kernel against its plain form ----------

@pytest.mark.parametrize("positions", [
    [0, 1], [5, 6], [7, 8], [6, 21], [23, 11], [39, 3]])
def test_window_decode_kernel_matches_the_plain_form(positions):
    """Positions at 0, inside the first window, at its edge, across pages:
    the kernel's walk from the window's first page against the gather, on a
    pool whose pages before the window hold what a stale read would show."""
    H, Dk, dv, W = 3, 40, 24, 128
    keys = jax.random.split(jax.random.key(11), 4)
    pool = jax.random.normal(keys[0], (2, 24, BLOCK, W), jnp.float32)
    tables = jnp.asarray([[3, 9, 4, 11, 5, 6, 7, 8, 10, 12],
                          [13, 2, 14, 1, 15, 16, 17, 18, 19, 20]], jnp.int32)
    t = jnp.asarray(positions, jnp.int32)
    # Pages wholly before a lane's window are given back: another's now.
    first = np.maximum(np.asarray(positions) - (WINDOW - 1), 0) // BLOCK
    stale = np.asarray(tables).copy()
    for lane, n in enumerate(first):
        stale[lane, :n] = 23
    pool = pool.at[:, 23].set(1e4)
    q = jax.random.normal(keys[1], (2, H, Dk), jnp.float32)
    cur = jax.random.normal(keys[2], (2, Dk), jnp.float32)
    kw = dict(value_dim=dv, scale=0.3, window=WINDOW)
    want = plain_ops.swa_latent_decode_attention(
        q, pool, jnp.int32(1), jnp.asarray(stale), t + 1, cur, **kw)
    got = latent.swa_latent_decode_attention_pallas(
        q, pool, jnp.int32(1), jnp.asarray(stale), t + 1, cur, **kw,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    # By hand: the softmax over the lane's last WINDOW rows, its own last.
    for lane, pos in enumerate(positions):
        rows = np.concatenate(
            [np.asarray(pool[1, tables[lane]]).reshape(-1, W)[:pos, :Dk],
             np.asarray(cur[lane])[None]])[-WINDOW:]
        s = np.asarray(q[lane]) @ rows.T * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(want[lane]), p @ rows[:, :dv],
                                   **TOL)


def test_window_table_names_the_pages_the_window_reaches():
    assert latent.window_pages(16, 513) == 33 and latent.window_pages(4, 7) == 3
    tables = jnp.arange(40, dtype=jnp.int32)[None, :] + 100
    for seq_len in (1, 7, 8, 9, 30, 160):
        got, lens, skip = latent.window_table(
            tables, jnp.asarray([seq_len]), BLOCK, WINDOW)
        first_row = max(seq_len - WINDOW, 0)
        assert int(got[0, 0]) == 100 + first_row // BLOCK
        assert int(skip[0]) == first_row % BLOCK
        assert int(lens[0]) == seq_len - first_row // BLOCK * BLOCK
        # Every cached row the query sees lies in the pages named.
        assert -(-(int(lens[0]) - 1) // BLOCK) <= got.shape[1]


# ---------- the owner of both kinds of cache layer ----------

def test_pool_bytes_follow_the_lanes_and_not_the_context():
    short = pages.PageGeometry.for_engine(CFG, 4, 64)
    long = pages.PageGeometry.for_engine(CFG, 4, 4096)
    wide = pages.PageGeometry.for_engine(CFG, 8, 64)
    assert long.pool_bytes > 50 * short.pool_bytes
    assert long.window == short.window
    assert short.window.lane_pages == 3             # ceil(7 / 4) + 1
    assert short.window.lane_stretches == 2         # ceil(3 / 8) + 1
    assert short.window.lanes == 4 + 2
    assert short.window.n_blocks == 1 + (6 + 1) * 2 * RUN
    assert wide.window.n_blocks == 1 + (10 + 1) * 2 * RUN
    got = short.describe()
    assert (got["kv_layers_full"], got["kv_layers_window"], got["window"]) \
        == (2, 3, 7)
    assert got["window_token_bytes"] == 128 * 4
    assert got["window_pool_bytes"] == 3 * 113 * 4 * 128 * 4
    assert got["kv_layers"] == 2 and got["index_token_bytes"] == 16 * 4
    assert any("prefix hits" in s for s in got["off_for_window_layers"])
    assert "window of the context" in short.one_chip_only
    # At the cell's widths (chipbench/configs/dots3-note-prev-cut.json).
    cell = dataclasses.replace(
        CFG, kv_block_size=16, window_attn=dataclasses.replace(
            CFG.window_attn, kv_lora_rank=1024, qk_rope_head_dim=64,
            window=513), dtype="bfloat16")
    geom = pages.PageGeometry.for_engine(cell, 64, 18432)
    assert geom.window.lane_pages == 34 and geom.window.lane_stretches == 6
    assert geom.window.token_bytes == 2304
    assert geom.window.n_blocks == 1 + 73 * 6 * 8 == 3505
    assert geom.window.pool_bytes == 3 * 3505 * 16 * 2304 < 0.5e9
    # Every other cache: no window pool, the allocators it had.
    plain = pages.PageGeometry.for_engine(configs.get_config("tiny-dsa"), 2, 64)
    assert plain.window is None and "window_pool_bytes" not in plain.describe()
    assert type(allocator_for(plain, True)) is PrefixCachingAllocator
    assert type(allocator_for(plain, False)) is BlockAllocator
    assert type(allocator_for(short, True)) is WindowedAllocator


def test_a_lane_holds_the_windows_pages_and_admission_reserves_by_kind():
    geom = pages.PageGeometry.for_engine(CFG, 2, 512)
    owner = allocator_for(geom, True)
    per, w = geom.max_blocks_per_seq, geom.window
    assert RUN * (owner.stretches.n_blocks - 1) == w.n_blocks - 1
    assert owner.lanes == 4 and owner.run == RUN
    # Four tables take the four reservations whatever their length; a fifth
    # finds no free block of the other kind either, and is refused.
    tables = [owner.alloc(n) for n in (per, 3, 1, 9)]
    assert owner.free_blocks == 0 and owner.tables == 4
    with pytest.raises(OutOfBlocks, match="reservation"):
        owner.alloc(1)
    owner.free(tables.pop())
    assert owner.free_blocks == owner.n_blocks - 1 - per - 4
    # A prompt of 200 in windows of 32, then 40 decode chunks of 4 steps:
    # the lane holds whole aligned stretches, those that hold a page in reach
    # and no other, its table row names them by logical page, and what it
    # gave back is handed out again.
    table, seen, taken = tables[0], set(), 0

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
        assert held_stretches() == list(range(
            max(hi - (WINDOW - 1), 0) // BLOCK // RUN,
            (hi - 1) // BLOCK // RUN + 1))
    pos = 200
    for _ in range(40):
        row = np.zeros(per, np.int32)
        owner.slide(table, pos, pos + 4, row)
        first, last = (pos - (WINDOW - 1)) // BLOCK, (pos + 3) // BLOCK
        assert held_stretches() == list(range(first // RUN, last // RUN + 1))
        assert len(table.window) <= w.lane_stretches * RUN
        lo, hi = table.first, table.first + len(table.window)
        assert list(row[lo:hi]) == table.window
        assert not row[:lo].any() and not row[hi:].any()
        taken = max(taken, last // RUN + 1)
        pos += 4
    # 12 stretches were written into; LIFO, two ids served them all.
    assert taken == 12 and len(seen) == 2 * RUN
    assert owner.window_used_fraction == (
        len(table.window) // RUN / (owner.stretches.n_blocks - 1))
    for t in tables:
        owner.free(t)
    assert owner.tables == 0
    assert owner.stretches.free_blocks == owner.stretches.n_blocks - 1
    assert owner.free_blocks == owner.n_blocks - 1


# ---------- the counters ----------

def _counters(telemetry, name, label):
    return {s.labels[label]: s.value
            for m in telemetry.registry.collect() for s in m.samples
            if s.name == name}


def test_window_counters_from_positions():
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
    from llm_d_inference_scheduler_tpu.engine.telemetry import (
        PROGRAM_COUNTERS, EngineTelemetry)

    assert "swa_rows" in PROGRAM_COUNTERS
    eng = object.__new__(TpuEngine)
    eng.cfg = EngineConfig(model="tiny-swa", max_batch=4, decode_chunk=4)
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
                suffix_len=np.asarray([6], np.int32))),
            (("prefill", 16), dict(          # a warm-up program: nobody's
                tokens=np.zeros((1, 16), np.int32), warm=True,
                slots=np.asarray([4], np.int32),
                seq_len=np.asarray([1], np.int32)))):
        real, queries = eng._requests_part(op, args)
        eng.telemetry.book_program(eng.bound.program_counts(
            op[0], args["slots" if op[0] == "decode" else "tokens"].size,
            args.get("steps", 1), real=real, queries=queries))
    contexts = [3, 4, 5, 41, 42, 43] + list(range(5, 11))
    assert _counters(eng.telemetry, "jetstream:swa_rows_total", "kind") == {
        "context": sum(contexts),
        "attended": sum(min(c, WINDOW) for c in contexts)}
    # The full layers' counters are the selecting block's own, as they were.
    assert _counters(eng.telemetry, "jetstream:dsa_rows_total", "kind") == {
        "scored": sum(contexts),
        "attended": sum(min(c, TOPK) for c in contexts)}
    # A model without window layers books nothing there.
    eng.bound = bind(configs.get_config("tiny-dsa"), platform="cpu")
    assert not any(n == "swa_rows" for n, _, _ in eng.bound.program_counts(
        "decode", 4, 2, real=1, queries=(np.asarray([5]), np.asarray([2]))))


# ---------- the engine, end to end ----------

@pytest.fixture
def served():
    """The model in float32 under a name an engine can be asked for."""
    name = "tiny-swa-f32"
    configs._REGISTRY[name] = dataclasses.replace(CFG, name=name)
    yield name
    del configs._REGISTRY[name]


@pytest.mark.parametrize("kernels", [True])
def test_engine_serves_through_windows_and_both_kinds_of_pool(served,
                                                              kernels):
    """Three prompts on two lanes (the third waits for a lane and takes the
    pages the first two gave back), written in windows of 8, decoded in
    chunks of 4 past the window and past index_topk: greedy tokens are the
    plain forward's; the window pool's gauge, counters and /health say what
    the positions do."""
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
                logits = jax.jit(lambda p, t: mla.forward(p, eng.mcfg, t)[0])(
                    eng.params, told)
                plain.append([int(logits[0, len(prompts[rid]) - 1 + i]
                                  .argmax()) for i in range(len(toks))])
            usage = [s.value for m in eng.telemetry.registry.collect()
                     for s in m.samples
                     if s.name == "jetstream:kv_window_cache_usage_perc"]
            return (got, plain, usage, eng.allocator,
                    _counters(eng.telemetry, "jetstream:swa_rows_total",
                              "kind"),
                    _counters(eng.telemetry,
                              "jetstream:mla_attention_tokens_total", "form"),
                    eng.describe()["settings"])
        finally:
            await eng.stop()

    got, plain, usage, owner, rows, attn, settings = asyncio.run(serve(
        EngineConfig(model=served, backend="tpu", max_batch=2,
                     max_model_len=96, decode_chunk=4, kv_events_port=0,
                     seed=7, prefill_chunk=8, pallas_attention=kernels,
                     pallas_interpret=kernels)))
    assert got == plain and [len(t) for t in got] == [14, 22, 9]
    # Every request gave everything back, of both kinds.
    assert usage == [0.0] and owner.tables == 0
    assert owner.stretches.free_blocks == owner.stretches.n_blocks - 1
    assert owner.free_blocks == owner.n_blocks - 1
    assert 0 < rows["attended"] < rows["context"]
    assert attn["expanded"] > 0 and attn["absorbed"] > 0
    assert (settings["kv_layers_full"], settings["kv_layers_window"],
            settings["window"]) == (2, 3, WINDOW)
    assert settings["window_attention"] == (
        "kernel_interpret" if kernels else "xla")
    assert settings["window_pool_bytes"] == 3 * (1 + 5 * 2 * RUN) * 4 * 128 * 4
    assert not settings["prefix_caching"]      # asked for or not


@pytest.mark.parametrize("extra", [dict(tp_size=2), dict(role="prefill"),
                                   dict(pp_size=2)])
def test_engine_refuses_what_a_window_pool_cannot_do(extra):
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    with pytest.raises(ValueError, match="window of the context"):
        TpuEngine(EngineConfig(model="tiny-swa", backend="tpu", max_batch=2,
                               max_model_len=64, kv_events_port=0, **extra))
