"""Grouped-matmul MoE FFN vs the dense-over-experts reference math, and the
rule that chooses between them.

Interpret-mode on CPU (same strategy as test_pallas_paged_attention.py); the
kernel is handed to the TPU compiler at Mixtral's widths in
test_chip_compile.py and checked on the chip by scripts/microbench_decode.py
--moe.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from llm_d_inference_scheduler_tpu.models import configs
from llm_d_inference_scheduler_tpu.models.configs import ModelConfig
from llm_d_inference_scheduler_tpu.models.llama import _moe_ffn
from llm_d_inference_scheduler_tpu.ops.pallas_moe import (
    moe_ffn_grouped,
    use_grouped,
)


def _mk(E=4, D=128, F=256, k=2, seed=0, dtype=jnp.float32):
    key = jax.random.key(seed)
    ks = jax.random.split(key, 4)
    lp = {
        "router": jax.random.normal(ks[0], (D, E), jnp.float32) * D ** -0.5,
        "w1": jax.random.normal(ks[1], (E, D, F), jnp.float32) * D ** -0.5,
        "w3": jax.random.normal(ks[2], (E, D, F), jnp.float32) * D ** -0.5,
        "w2": jax.random.normal(ks[3], (E, F, D), jnp.float32) * F ** -0.5,
    }
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    cfg = ModelConfig(name="t", vocab_size=8, d_model=D, n_layers=1,
                      n_heads=2, n_kv_heads=1, d_ff=F, n_experts=E,
                      experts_per_token=k)
    return lp, cfg


@pytest.mark.parametrize("shape", [(1, 1), (2, 3), (4, 8)])
def test_grouped_matches_dense(shape):
    B, S = shape
    lp, cfg = _mk()
    x = jax.random.normal(jax.random.key(7), (B, S, cfg.d_model), jnp.float32)
    dense = _moe_ffn(cfg, lp, x)
    grouped = moe_ffn_grouped(lp, x, cfg.n_experts, cfg.experts_per_token,
                              tm=8, interpret=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("E,k,favoured", [
    (4, 1, (2,)),        # every token on one expert: maximally ragged groups
    (8, 2, (2, 5)),      # every token to the same two experts, six empty
    (8, 2, None),        # one expert never chosen
    (8, 3, (7, 0, 4)),   # the last and the first expert: the groups' ends
    (16, 4, (15, 0, 8, 3)),   # four of sixteen, the first and last among them
])
def test_grouped_skewed_routing(E, k, favoured):
    """Routing skewed to the limit drops no token: the layout is static and
    only the offsets are data."""
    lp, cfg = _mk(E=E, k=k)
    if favoured is None:
        lp["router"] = lp["router"].at[:, 3].add(-100.0)
    else:
        for rank, e in enumerate(favoured):
            lp["router"] = lp["router"].at[:, e].add(100.0 - 10 * rank)
    x = jax.random.normal(jax.random.key(9), (2, 40, cfg.d_model), jnp.float32)
    # The bias is on the router's weights, so the sign of a token's sum
    # decides: shift the rows so that every token's sum is positive.
    x = jnp.abs(x)
    top = jax.lax.top_k(x.reshape(-1, cfg.d_model) @ lp["router"], k)[1]
    chosen = set(np.asarray(top).reshape(-1).tolist())
    assert chosen == set(favoured) if favoured else 3 not in chosen
    dense = _moe_ffn(cfg, lp, x)
    grouped = moe_ffn_grouped(lp, x, cfg.n_experts, k, tm=16, interpret=True,
                              tiles_down=(128, 128))
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("tokens", [256, 512, 1024])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_at_the_cells_shape_ratios(tokens, dtype):
    """A cut of mixtral-8x7b-cut.batch-full's prefill: 8 experts, top 2, one
    prompt of 256 / 512 / 1,024 rows, the served row tile, small D and F. In
    f32 the two forms agree to rounding of the sum's order; in bf16 the
    grouped form is no further from the f32 answer than the dense form is."""
    lp32, cfg = _mk(E=8, k=2)
    x32 = jax.random.normal(jax.random.key(tokens), (1, tokens, cfg.d_model),
                            jnp.float32)
    ref = np.asarray(_moe_ffn(cfg, lp32, x32))
    dt = jnp.dtype(dtype)
    lp = jax.tree.map(lambda a: a.astype(dt), lp32)
    x = x32.astype(dt)
    dense = np.asarray(_moe_ffn(cfg, lp, x), np.float32)
    grouped = np.asarray(moe_ffn_grouped(
        lp, x, cfg.n_experts, cfg.experts_per_token, interpret=True,
        tiles_down=(128, 128)), np.float32)   # two K tiles: the f32 scratch
    if dtype == "float32":
        np.testing.assert_allclose(grouped, dense, atol=1e-5, rtol=1e-5)
        return
    # The grouped form routes on the product's f32 logits (what the dense
    # form's cast compiles to on a TPU); on the CPU the dense form rounds
    # them to bf16 first, so a token whose second and third logits lie
    # within that rounding takes another expert there. Such tokens are few,
    # and are no statement about the kernel: compare the others.
    xt = x.reshape(tokens, -1)
    pick = lambda logits: np.sort(np.asarray(jax.lax.top_k(logits, 2)[1]), -1)
    same = (pick(jnp.dot(xt, lp["router"], preferred_element_type=jnp.float32))
            == pick((xt @ lp["router"]).astype(jnp.float32))).all(-1)
    assert same.mean() > 0.98
    assert (np.abs(grouped - ref)[0, same].max()
            <= 1.5 * np.abs(dense - ref)[0, same].max() + 1e-3)
    np.testing.assert_allclose(grouped[0, same], dense[0, same],
                               atol=3e-2, rtol=3e-2)


_MIXTRAL = dict(n_experts=8, experts_per_token=2, d_model=4096, d_ff=14336)


@pytest.mark.parametrize("tokens,facts,grouped", [
    # The decode chunk's lanes and the 16-token prefill bucket: under the
    # ridge both forms are the read of every expert's weights.
    (2, {}, False), (4, {}, False), (8, {}, False), (16, {}, False),
    (128, {}, False),
    # The prefill buckets past the ridge.
    (512, {}, True), (1024, {}, True), (4 * 512, {}, True),
    # Sharded weights keep the einsums XLA partitions.
    (1024, {"sharded": True}, False),
    # No kernel off the TPU, unless a test interprets it.
    (1024, {"platform": "cpu"}, False),
    (1024, {"platform": "cpu", "interpret": True}, True),
    (16, {"platform": "cpu", "interpret": True}, False),
    # Nothing to skip where every expert is chosen; a dense model has none.
    (1024, {"experts_per_token": 8}, False),
    (1024, {"n_experts": 0}, False),
    # Widths the kernel cannot tile.
    (1024, {"d_ff": 200}, False),
])
def test_the_form_is_a_function_of_shape_platform_and_sharding(
        tokens, facts, grouped):
    kw = {**_MIXTRAL, "platform": "tpu", "sharded": False, **facts}
    assert use_grouped(tokens, **kw) is grouped


def test_engine_serves_the_form_its_shapes_call_for(monkeypatch):
    """A Mixtral-shaped tiny engine (8 experts, top 2): the 512-token prefill
    bucket traces the grouped form, the decode chunk the dense one, the
    counter reads both programs' padded token counts, and the greedy tokens
    are those of an engine that runs dense throughout."""
    import asyncio

    from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
    from llm_d_inference_scheduler_tpu.models import llama

    mcfg = dataclasses.replace(configs.TINY_MOE, name="tiny-mixtral",
                               n_experts=8, max_seq_len=1024)
    monkeypatch.setitem(configs._REGISTRY, mcfg.name, mcfg)
    # f32 params: keeps greedy argmax insensitive to the two forms' different
    # rounding points (bf16 tolerance is test_grouped_bf16's).
    params = llama.init_params(mcfg, jax.random.key(11), dtype=jnp.float32)
    prompt = [1 + (7 * i) % 500 for i in range(300)]

    async def run(interpret: bool):
        cfg = EngineConfig(model=mcfg.name, backend="tpu", max_batch=2,
                           max_model_len=1024, seed=11, decode_chunk=4,
                           warmup=False, pallas_interpret=interpret,
                           pallas_attention=False, kv_events_port=0)
        eng = TpuEngine(cfg, params=params)
        await eng.start()
        try:
            out = eng.submit(EngineRequest(
                request_id="moe", prompt_token_ids=prompt, max_tokens=6,
                temperature=0.0, ignore_eos=True))
            got = []
            while True:
                ev = await out.get()
                if ev.token_id is not None:
                    got.append(ev.token_id)
                if ev.finish_reason is not None:
                    break
            return got, eng
        finally:
            await eng.stop()

    def counted(eng):
        text = eng.telemetry.render().decode()
        return {form: sum(float(ln.split()[-1]) for ln in text.splitlines()
                          if ln.startswith("jetstream:moe_ffn_tokens_total{")
                          and f'form="{form}"' in ln)
                for form in ("grouped", "dense")}

    dense, dense_eng = asyncio.run(run(False))
    grouped, eng = asyncio.run(run(True))
    assert len(dense) == 6
    assert grouped == dense
    # One prompt of 300 tokens pads to the 512 bucket; its first token comes
    # with the prefill, the other five from two chunks of 4 steps on 2 lanes.
    assert eng.bound.model_for(512).moe_impl == "grouped_interpret"
    assert eng.bound.model_for(2).moe_impl == "dense"
    assert counted(eng) == {"grouped": 512.0, "dense": 2 * 4 * 2.0}
    # Off the TPU and not interpreting, every program is dense.
    assert counted(dense_eng) == {"grouped": 0.0, "dense": 512 + 2 * 4 * 2.0}


def test_grouped_rejects_unaligned_dff():
    """F with no 128-aligned divisor must raise, not silently drop columns."""
    lp, cfg = _mk(D=128, F=192)
    x = jax.random.normal(jax.random.key(1), (1, 2, cfg.d_model), jnp.float32)
    with pytest.raises(ValueError, match="no tile"):
        moe_ffn_grouped(lp, x, cfg.n_experts, cfg.experts_per_token,
                        interpret=True)


def test_grouped_nondefault_tile_divisor():
    """F=384 has one lane-aligned divisor beside 128 (384): the tail columns
    must be computed."""
    lp, cfg = _mk(D=128, F=384)
    x = jax.random.normal(jax.random.key(2), (2, 3, cfg.d_model), jnp.float32)
    dense = _moe_ffn(cfg, lp, x)
    grouped = moe_ffn_grouped(lp, x, cfg.n_experts, cfg.experts_per_token,
                              tm=8, interpret=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_grouped_bf16():
    lp, cfg = _mk(dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(3), (2, 4, cfg.d_model), jnp.bfloat16)
    dense = _moe_ffn(cfg, lp, x)
    grouped = moe_ffn_grouped(lp, x, cfg.n_experts, cfg.experts_per_token,
                              tm=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(grouped, np.float32), np.asarray(dense, np.float32),
        atol=3e-2, rtol=3e-2)


# ---------- a held range of the experts, and experts that are not gated ----------

def _plain_experts(lp, x, idx, gates, first, count, gated, reglu=False):
    """Token by token, expert by expert: the held experts' part of the
    result (``reglu``: a gated expert's activation is relu, not silu)."""
    out = np.zeros(x.shape, np.float32)
    x, idx, gates = (np.asarray(a) for a in (x, idx, gates))
    w1, w2 = np.asarray(lp["w1"]), np.asarray(lp["w2"])
    for t in range(x.shape[0]):
        for j, e in enumerate(idx[t]):
            if not first <= e < first + count:
                continue
            up = x[t] @ w1[e - first]
            if gated:
                act = np.maximum(up, 0.0) if reglu else up / (1 + np.exp(-up))
                h = act * (x[t] @ np.asarray(lp["w3"])[e - first])
            else:
                h = np.square(np.maximum(up, 0.0))
            out[t] += gates[t, j] * (h @ w2[e - first])
    return out


@pytest.mark.parametrize("act", ["swiglu", "relu2", "reglu"])
@pytest.mark.parametrize("first,count,routed_over", [
    (0, 4, 4),       # every expert held, named as a range
    (4, 4, 16),      # the second quarter of sixteen
    (12, 4, 16),     # the last quarter: absent rows sort behind nothing
    (3, 2, 8),       # a range aligned to nothing
    (0, 1, 16),      # one held expert: a single group, most rows absent
    (5, 3, 8),       # the range's end is the router's last output
])
def test_grouped_experts_on_a_held_range(act, first, count, routed_over):
    """Routing is over all the experts; the rows of absent ones are dropped
    ahead of the group layout and contribute nothing, whatever lies in the
    buffer where no tile wrote."""
    from llm_d_inference_scheduler_tpu.ops.pallas_moe import grouped_experts

    lp, _ = _mk(E=count, D=128, F=128, seed=3)
    T, k = 37, 3
    keys = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(keys[0], (T, 128), jnp.float32)
    idx = jnp.argsort(jax.random.uniform(keys[1], (T, routed_over)),
                      axis=-1)[:, :k].astype(jnp.int32)
    gates = jax.random.uniform(keys[2], (T, k), jnp.float32, 0.2, 1.0)
    gated, reglu = act != "relu2", act == "reglu"
    got = grouped_experts(lp, x, idx, gates, count, first=first, gated=gated,
                          reglu=reglu, tm=8, interpret=True)
    want = _plain_experts(lp, x, idx, gates, first, count, gated, reglu)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5, rtol=3e-5)
    held = (np.asarray(idx) >= first) & (np.asarray(idx) < first + count)
    assert held.any() and (routed_over == count or not held.all())
    # Tokens none of whose experts live here get exactly nothing.
    nothing = ~held.any(axis=1)
    assert not np.asarray(got)[nothing].any()


def test_every_choice_absent_gives_zeros_and_no_token_is_dropped_at_any_skew():
    from llm_d_inference_scheduler_tpu.ops.pallas_moe import grouped_experts

    lp, _ = _mk(E=2, D=128, F=128, seed=4)
    x = jax.random.normal(jax.random.key(6), (24, 128), jnp.float32)
    gates = jnp.ones((24, 2), jnp.float32)
    absent = jnp.tile(jnp.asarray([[0, 7]], jnp.int32), (24, 1))
    got = grouped_experts(lp, x, absent, gates, 2, first=4, gated=False,
                          tm=8, interpret=True)
    assert not np.asarray(got).any()
    # Every token on the one held expert 5: its group takes all 24 rows.
    skew = jnp.tile(jnp.asarray([[5, 0]], jnp.int32), (24, 1))
    got = grouped_experts(lp, x, skew, gates, 2, first=4, gated=False,
                          tm=8, interpret=True)
    want = _plain_experts(lp, x, skew, gates, 4, 2, False)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5, rtol=3e-5)
    assert np.abs(want).min(axis=1).max() > 0


def test_every_choice_absent_keeps_the_kernels_block_indices_in_the_buffer(
        monkeypatch):
    """A program none of whose choices is held has no group. The kernel's
    block index is min(i, n_live - 1): with no live tile it is -1, which the
    interpreter clamps and the chip halts on (a bounds check of the row
    tile's copy; longcat-flash-omni-cut, 2 of 6 seeds, PR 39). So what
    reaches the kernel names at least one tile, and never more than it has."""
    from llm_d_inference_scheduler_tpu.ops import pallas_moe

    seen = []
    kernel = pallas_moe._grouped_matmul

    def watched(lhs, rhs, layer, tile_expert, n_live, **kw):
        seen.append((lhs.shape[0] // kw["tm"], int(n_live[0]),
                     np.asarray(tile_expert)))
        return kernel(lhs, rhs, layer, tile_expert, n_live, **kw)

    monkeypatch.setattr(pallas_moe, "_grouped_matmul", watched)
    lp, _ = _mk(E=2, D=128, F=128, seed=4)
    x = jax.random.normal(jax.random.key(6), (24, 128), jnp.float32)
    gates = jnp.ones((24, 2), jnp.float32)
    for choices in ([0, 7], [5, 0], [4, 5]):
        idx = jnp.tile(jnp.asarray([choices], jnp.int32), (24, 1))
        pallas_moe.grouped_experts(lp, x, idx, gates, 2, first=4, gated=False,
                                   tm=8, interpret=True)
    assert len(seen) == 6
    for tiles, live, tile_expert in seen:
        assert 1 <= live <= tiles
        assert ((0 <= tile_expert) & (tile_expert < 2)).all()
    assert [live for _, live, _ in seen] == [1, 1, 3, 3, 6, 6]


# ---------- the layout from counting against the sort-built one ----------

def _sorted_layout(flat_expert, E, tm):
    """The group-padded layout as PR 31-54 built it: a stable sort of the
    rows by expert, a bincount, two cumulative sums and two integer
    scatters. ``flat_expert`` [N] with E for a row whose expert is absent.
    Returns (dest [N], the row that stands at each place of the buffer or -1
    [Tp], tile_expert, n_live before the floor of one tile)."""
    N = flat_expert.shape[0]
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    counts = jnp.bincount(flat_expert, length=E)
    padded = ((counts + tm - 1) // tm) * tm
    zero = jnp.zeros((1,), counts.dtype)
    off = jnp.concatenate([zero, jnp.cumsum(padded)])
    start = jnp.concatenate([zero, jnp.cumsum(counts)])
    dest_sorted = off[sorted_expert] + jnp.arange(N) - start[sorted_expert]
    Tp = N + E * tm
    row_at = jnp.full((Tp + N,), -1, jnp.int32).at[dest_sorted].set(
        order.astype(jnp.int32))[:Tp]
    dest = jnp.zeros((N,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))
    tile_starts = jnp.arange(Tp // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(off[1:], tile_starts, side="right"), E - 1)
    return (np.asarray(dest), np.asarray(row_at), np.asarray(tile_expert),
            int(off[E] // tm))


def _routing(kind, T, k, E, routed_over, seed):
    """[k, T] experts 0 .. E - 1, E where a choice's expert is not held."""
    rng = np.random.default_rng(seed)
    if kind == "random":
        idx = np.argsort(rng.random((T, routed_over)), axis=-1)[:, :k]
    elif kind == "skewed":          # most tokens on the first two experts
        pull = (np.arange(routed_over) < 2) * (rng.random((T, 1)) < 0.9)
        idx = np.argsort(rng.random((T, routed_over)) - pull, axis=-1)[:, :k]
    elif kind == "one_takes_all":   # every token's first choice is expert 1
        idx = np.argsort(rng.random((T, routed_over)), axis=-1)[:, :k]
        idx = np.where(idx == 1, idx[:, :1], idx)
        idx[:, 0] = 1
    elif kind == "all_absent":
        idx = np.full((T, k), routed_over)
    return jnp.asarray(np.where(idx < E, idx, E).T, jnp.int32)


@pytest.mark.parametrize("T,k,E,routed_over,tm", [
    (37, 3, 4, 4, 8),        # every expert held; rows that fill no count block
    (256, 4, 32, 32, 16),    # lfm2's ratios: 32 narrow groups, 8 count blocks
    (200, 6, 8, 64, 8),      # a held range: most choices absent
    (64, 2, 3, 5, 128),      # the served row tile, groups far under a tile
])
@pytest.mark.parametrize("kind", ["random", "skewed", "one_takes_all",
                                  "all_absent"])
def test_the_counted_layout_is_the_sorted_one(kind, T, k, E, routed_over, tm):
    """Row for row: a row's place, the row at each place that holds one,
    the tile -> expert map and the live tiles are what the stable sort and
    the scatters gave, at every skew; no row is dropped and no two share a
    place."""
    from llm_d_inference_scheduler_tpu.ops.pallas_moe import group_layout

    absent = routed_over != E or kind == "all_absent"
    if kind == "all_absent" and routed_over == E:
        pytest.skip("a chip that holds every expert has no absent choice")
    expert = _routing(kind, T, k, E, routed_over, seed=T + k)
    dest, src, tile_expert, n_live = (np.asarray(a) for a in jax.jit(
        lambda e: group_layout(e, E, tm=tm, absent=absent))(expert))
    flat = np.asarray(expert).reshape(-1)
    want_dest, row_at, want_tiles, want_live = _sorted_layout(
        jnp.asarray(flat), E, tm)
    np.testing.assert_array_equal(dest, want_dest)
    # (Whole tiles: one more than the sorted one's where k*T fills no tile.)
    np.testing.assert_array_equal(tile_expert[:want_tiles.size], want_tiles)
    assert tile_expert.size == -(-flat.size // tm) + E
    assert n_live[0] == max(want_live, 1 if absent else 0)
    assert len(set(dest.tolist())) == dest.size      # nobody shares a place
    held = flat < E
    assert (dest[held] < want_live * tm).all()           # inside a live tile
    assert (tile_expert[dest[held] // tm] == flat[held]).all()
    assert (dest[~held] >= want_live * tm).all()     # behind the last group
    # The way in: a place that holds a row names that row's token (a row of
    # the choice-major order is token row % T).
    holds = row_at >= 0
    holds[want_live * tm:] = False          # absent rows: no tile reads them
    assert src.size == -(-row_at.size // tm) * tm        # whole tiles
    np.testing.assert_array_equal(src[:row_at.size][holds], row_at[holds] % T)
    assert ((0 <= src) & (src < T)).all()
    if kind == "one_takes_all":
        assert np.bincount(flat, minlength=E + 1)[1] == T


def test_the_layout_is_made_without_a_scatter_or_a_loop():
    """What ISSUE 55 took out stays out: the layout's program holds one
    sort, and no scatter (an integer scatter is a few microseconds of fixed
    cost on the chip whatever its size), no while (a searchsorted) and no
    second sort."""
    from llm_d_inference_scheduler_tpu.ops.pallas_moe import group_layout

    for absent in (False, True):
        text = jax.jit(lambda e: group_layout(e, 32, absent=absent)).lower(
            jax.ShapeDtypeStruct((4, 1024), jnp.int32)).as_text()
        assert "scatter" not in text and "while" not in text
        assert text.count("stablehlo.sort") == 1
        assert text.count("stablehlo.dot_general") == 1   # the count
        # One gather of integers (a place's row out of the sorted keys); the
        # two of D-wide rows are the caller's.
        assert text.count('"stablehlo.gather"(') == 1


# ---------- few rows: dense over the held experts that a row chose ----------

def _chosen_case(routing, T, count=8, routed_over=256, k=3, seed=9):
    """(idx [T, k] over ``routed_over`` outputs, real [T]) of a seeded
    routing that leaves held experts (``first`` 4, ``count`` of them)
    without a row."""
    first = 4
    rng = np.random.default_rng(seed + T)
    real = np.ones((T,), bool)
    if routing == "some_empty":       # even routing: some held experts idle
        idx = np.argsort(rng.random((T, routed_over)), axis=1)[:, :k]
    elif routing == "all_but_one":    # one held expert takes every row's pick
        idx = np.stack([np.full((T,), first + 2), np.zeros((T,), int),
                        np.full((T,), routed_over - 1)], axis=1)
    elif routing == "none":           # nobody chose an expert held here
        idx = np.stack([np.arange(T) % first, np.full((T,), 9 + count),
                        np.full((T,), 12 + count)], axis=1)
    elif routing == "padding_lane":   # the last row alone names expert 5 ...
        idx = np.stack([np.full((T,), first), np.zeros((T,), int),
                        np.full((T,), routed_over - 1)], axis=1)
        idx[-1, 1] = first + 1
        real[-1] = False              # ... and it is nobody's
    return jnp.asarray(idx, jnp.int32), real, first


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("T", [2, 32, 64])
@pytest.mark.parametrize("routing", ["some_empty", "all_but_one", "none",
                                     "padding_lane"])
def test_chosen_experts_match_dense_and_read_no_expert_nobody_chose(
        routing, T, gated, monkeypatch):
    """The form of few rows against the plain sum over (token, held expert):
    the same result at any routing, no token dropped, and only the experts a
    row of somebody's chose reach the kernel -- at least one tile, so that no
    block index is -1 (the chip halts on it; the interpreter does not)."""
    from llm_d_inference_scheduler_tpu.ops import pallas_moe

    count = 8
    lp, _ = _mk(E=count, D=128, F=128, seed=3)
    idx, real, first = _chosen_case(routing, T, count)
    keys = jax.random.split(jax.random.key(T), 2)
    x = jax.random.normal(keys[0], (T, 128), jnp.float32)
    gates = jax.random.uniform(keys[1], idx.shape, jnp.float32, 0.2, 1.0)
    here = (idx >= first) & (idx < first + count)
    local = jnp.where(here & jnp.asarray(real)[:, None], idx - first, -1)

    seen = []
    kernel = pallas_moe._grouped_matmul

    def watched(lhs, rhs, layer, tile_expert, n_live, **kw):
        seen.append((int(n_live[0]), np.asarray(tile_expert)))
        return kernel(lhs, rhs, layer, tile_expert, n_live, **kw)

    monkeypatch.setattr(pallas_moe, "_grouped_matmul", watched)
    got, read = pallas_moe.chosen_experts(lp, x, local, gates, count,
                                          gated=gated, interpret=True)
    want = _plain_experts(lp, x, idx, gates, first, count, gated)
    want[~real] = 0.0                 # a row that is nobody's gets nothing
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5, rtol=3e-5)

    chosen = sorted(set(np.asarray(local).ravel().tolist()) - {-1})
    assert len(chosen) == {"all_but_one": 1, "none": 0,
                           "padding_lane": 1}.get(routing, len(chosen))
    assert len(chosen) < count        # every case leaves experts unread
    assert int(read) == max(len(chosen), 1)
    assert len(seen) == 2             # up (and gate), then down
    for live, tile_expert in seen:
        assert live == int(read) and 1 <= live <= count
        assert tile_expert[:len(chosen)].tolist() == chosen
        assert ((0 <= tile_expert) & (tile_expert < count)).all()
    if routing == "none":
        assert not np.asarray(got).any()
