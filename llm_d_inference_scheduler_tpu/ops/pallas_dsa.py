"""Pallas TPU kernels of learned sparse attention (DeepSeek-V3.2's; the plain
forms and the mathematics are ops/sparse_attention.py's).

**The indexer's scores.** ``I[s, t] = sum_j w[s, j] relu(q[s, j] . k[t])``
over 64 light heads: the per-head products are a [heads, S, T] tensor (4.3 GB
for a 1,024-token window over 16k rows) that must not reach HBM. A grid step
takes a tile of queries with their heads flattened onto the rows ([queries x
heads, 128]) against a tile of keys, and the product, the relu, the weight
(one a row) and the sum over a query's heads all happen in VMEM; what leaves
is [queries, keys] f32. One query a lane (decode) walks the lane's key pages
by its block table as the attention kernels walk the latent pages (a program
a lane, stages of P pages double-buffered), so that a step reads a context's
keys once, 256 B a token a layer, and nothing of the table's width beyond
the lane's length. Both decode walks fetch a stage as the latent kernel does
(``stage_fetch``: adjacent pages of the table as one copy).

**A window's attention over the selected rows.** The expanded form of a run
of queries (a prefill or continuation window) against every row carried out
to its keys and values, the selection a mask over (query, row): a tile of
queries against a tile of rows a grid step, a few heads at once (they share
the mask's tile), the softmax running in VMEM, so that the [heads, S, T]
scores (8.6 GB at 128 heads, 1,024 queries and 16k rows) exist a tile at a
time. A tile none of whose (query, row) is kept -- rows behind the causal
edge, the padding of the prior table's bucket -- is skipped.

**Decode attention over the selected rows.** ops/pallas_latent_attention.py's
walk (a program a lane, stages of P pages double-buffered, a running softmax
in the absorbed form) with one more operand, the lane's selection: a row that
was not selected scores minus infinity AND weighs nothing (a stage none of
whose rows is selected must leave the running state as it was, and so must a
current token that lost to 2,048 others). Whole pages are read and masked:
at 16 tokens a page, 2,048 rows chosen of some 10k leave few pages without a
chosen one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_latent_attention import (pages_per_stage, run_pages,
                                      stage_fetch, table_runs)
from .pallas_paged_attention import NEG_INF

# Queries a tile of the indexer (their heads flattened: 32 x 64 = 2,048 rows
# to the MXU) and keys a tile; the f32 product of one tile is 4 MB of VMEM.
QUERY_TILE = 32
KEY_TILE = 512
_INDEX_VMEM_BYTES = 48 * 2 ** 20


def _index_kernel(q_ref, w_ref, k_ref, out_ref, *, heads: int):
    q = q_ref[0]                                      # [St * Hi, Di]
    per_head = jax.lax.dot_general(
        q, k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [St * Hi, Tt]
    weighed = jnp.maximum(per_head, 0.0) * w_ref[0]   # w [St * Hi, 1]
    out_ref[0] = weighed.reshape(-1, heads, weighed.shape[-1]).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores_pallas(q: jnp.ndarray,     # [B, S, Hi, Di]
                        w: jnp.ndarray,     # [B, S, Hi] f32
                        keys: jnp.ndarray,  # [B, T, Di]
                        *, interpret: bool = False) -> jnp.ndarray:
    """ops/sparse_attention.index_scores, as a kernel: [B, S, T] f32."""
    B, S, Hi, Di = q.shape
    T = keys.shape[1]
    st = min(S, QUERY_TILE)
    tt = min(KEY_TILE, -(-T // 128) * 128)
    s_pad, t_pad = -(-S // st) * st, -(-T // tt) * tt
    q = jnp.pad(q, ((0, 0), (0, s_pad - S), (0, 0), (0, 0)))
    w = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, s_pad - S), (0, 0)))
    keys = jnp.pad(keys.astype(q.dtype), ((0, 0), (0, t_pad - T), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_index_kernel, heads=Hi),
        grid=(B, s_pad // st, t_pad // tt),
        in_specs=[
            pl.BlockSpec((1, st * Hi, Di), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, st * Hi, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tt, Di), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, st, tt), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, s_pad, t_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_INDEX_VMEM_BYTES),
        interpret=interpret,
        # The op's name in a device trace, for whoever reduces one.
        name="dsa_index_scores_window",
    )(q.reshape(B, s_pad * Hi, Di), w.reshape(B, s_pad * Hi, 1), keys)
    return out[:, :S, :T]


def _index_paged_kernel(bt_ref, run_ref, sl_ref, layer_ref,  # prefetch
                        q_ref, w_ref,                # [1, Hi, Di], [1, Hi, 1]
                        pool_hbm,                    # [L, N, block, Di] (ANY)
                        out_ref,                     # [1, stages, P * block]
                        tile, sem,
                        *, max_blocks: int, pages: int, block: int,
                        group: int):
    b = pl.program_id(0)
    q, w = q_ref[0], w_ref[0]
    n_pages = pl.cdiv(sl_ref[b] - 1, block)           # the lane's cached rows
    n_stages = pl.cdiv(n_pages, pages)

    # A last stage's pages past the lane's length are never fetched: they
    # score what the slot held, and the caller masks rows by length.
    _start, _wait = stage_fetch(
        bt_ref, run_ref, pool_hbm, tile, sem, lane=b, layer=layer_ref[0],
        n_pages=n_pages, max_blocks=max_blocks, group=group, zero_rest=False)

    # Stages past the lane's length are nobody's to read: zeros, not what
    # VMEM held.
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(n_stages > 0)
    def _prologue():
        _start(0, 0)

    def stage_body(s, carry):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < n_stages)
        def _prefetch_next():
            _start(s + 1, 1 - slot)

        _wait(s, slot)
        per_head = jax.lax.dot_general(
            q, tile[slot].reshape(pages * block, -1), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [Hi, P * block]
        out_ref[0, pl.ds(s, 1), :] = jnp.sum(
            jnp.maximum(per_head, 0.0) * w, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, n_stages, stage_body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores_paged_pallas(
    q: jnp.ndarray,             # [B, Hi, Di] — one query a lane
    w: jnp.ndarray,             # [B, Hi] f32
    pages: jnp.ndarray,         # [L, N, block, Di] — the indexer's key pool
    layer: jnp.ndarray,         # int32 scalar
    block_tables: jnp.ndarray,  # [B, maxB] int32
    seq_lens: jnp.ndarray,      # [B] int32 (incl. current token)
    *, interpret: bool = False,
) -> jnp.ndarray:
    """ops/sparse_attention.index_scores of every lane's query against its
    cached keys, read by the block table: [B, maxB * block] f32, meaningful
    at the lane's ``seq_lens - 1`` cached rows (the rest: masked by whoever
    selects)."""
    B, Hi, Di = q.shape
    _, _, block, _ = pages.shape
    maxB = block_tables.shape[1]
    n_pages = pages_per_stage(block, Di, pages.dtype.itemsize, maxB)
    group = run_pages(n_pages)
    rows = n_pages * block
    stages = -(-maxB // n_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hi, Di), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, Hi, 1), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, stages, rows), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n_pages, block, Di), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_index_paged_kernel, max_blocks=maxB, pages=n_pages,
                          block=block, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, stages, rows), jnp.float32),
        interpret=interpret,
        name="dsa_index_scores_decode",
    )(block_tables.reshape(-1),
      table_runs(block_tables, seq_lens, block, group).reshape(-1), seq_lens,
      jnp.asarray(layer, jnp.int32).reshape(1), q.astype(pages.dtype),
      w.astype(jnp.float32)[..., None], pages)
    return out.reshape(B, stages * rows)[:, :maxB * block]


# A window's attention: queries and rows a tile, heads a grid step.
WINDOW_QUERIES = 1024
WINDOW_ROWS = 1024
WINDOW_HEADS = 4
_WINDOW_VMEM_BYTES = 64 * 2 ** 20


def _window_kernel(live_ref,                      # scalar prefetch
                   qn_ref, qr_ref,                # [1, G, Sq, dn], [.., dr]
                   kn_ref, kr_ref, v_ref,         # [1, G, Tk, dn], [1, Tk, dr],
                   keep_ref,                      # [1, G, Tk, dv]; [1, Sq, Tk]
                   out_ref,                       # [1, G, Sq, dv]
                   m_sc, l_sc, acc_sc, *, heads: int, scale: float):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n_i, n_j = pl.num_programs(2), pl.num_programs(3)

    @pl.when(j == 0)
    def _start():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(live_ref[(b * n_i + i) * n_j + j] > 0)
    def _tile():
        keep = keep_ref[0].astype(jnp.float32) > 0            # [Sq, Tk]
        k_rope = kr_ref[0]
        over_d = (((1,), (1,)), ((), ()))
        for g in range(heads):
            s = (jax.lax.dot_general(qn_ref[0, g], kn_ref[0, g], over_d,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[0, g], k_rope, over_d,
                                       preferred_element_type=jnp.float32))
            s = jnp.where(keep, s * scale, NEG_INF)
            m = m_sc[g]
            new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # A row nothing of which is kept so far has new_m at NEG_INF and
            # exp(0) everywhere: kept out by the mask, not by the exponent.
            p = jnp.where(keep, jnp.exp(s - new_m), 0.0)
            corr = jnp.exp(m - new_m)
            l_sc[g] = l_sc[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_sc[g] = acc_sc[g] * corr + jnp.dot(
                p.astype(v_ref.dtype), v_ref[0, g],
                preferred_element_type=jnp.float32)
            m_sc[g] = new_m

    @pl.when(j == n_j - 1)
    def _finish():
        out_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                      ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "name"))
def masked_window_attention_pallas(
    q_nope: jnp.ndarray,   # [B, H, S, dn]
    q_rope: jnp.ndarray,   # [B, H, S, dr]
    k_nope: jnp.ndarray,   # [B, H, T, dn]
    k_rope: jnp.ndarray,   # [B, T, dr] — one rotated key part for all heads
    v: jnp.ndarray,        # [B, H, T, dv]
    keep: jnp.ndarray,     # [B, S, T] bool — rows a query attends to
    *,
    scale: float,
    interpret: bool = False,
    name: str = "dsa_window_attention",
) -> jnp.ndarray:
    """ops/sparse_attention.masked_window_attention, as a kernel: [B, H, S,
    dv] in q_nope.dtype. ``name`` is the op's in a device trace: the layers
    that attend to a band of the context call it as ``swa_window_attention``
    (tiles wholly outside the band are skipped, as those nothing selected
    are)."""
    B, H, S, dn = q_nope.shape
    T, dr, dv = k_nope.shape[2], q_rope.shape[-1], v.shape[-1]
    sq = min(WINDOW_QUERIES, -(-S // 32) * 32)
    tk = min(WINDOW_ROWS, -(-T // 128) * 128)
    G = WINDOW_HEADS
    while H % G:
        G //= 2
    s_pad, t_pad = -(-S // sq) * sq, -(-T // tk) * tk
    n_i, n_j = s_pad // sq, t_pad // tk

    def padded(x, axis, to):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, to - x.shape[axis])
        return jnp.pad(x, pad)

    q_nope, q_rope = padded(q_nope, 2, s_pad), padded(q_rope, 2, s_pad)
    k_nope, v = padded(k_nope, 2, t_pad), padded(v, 2, t_pad)
    k_rope = padded(k_rope.astype(q_rope.dtype), 1, t_pad)
    keep = padded(padded(keep, 1, s_pad), 2, t_pad)
    live = jnp.any(keep.reshape(B, n_i, sq, n_j, tk), axis=(2, 4))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // G, n_i, n_j),
        in_specs=[
            pl.BlockSpec((1, G, sq, dn), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, G, sq, dr), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, G, tk, dn), lambda b, h, i, j, *_: (b, h, j, 0)),
            pl.BlockSpec((1, tk, dr), lambda b, h, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, G, tk, dv), lambda b, h, i, j, *_: (b, h, j, 0)),
            pl.BlockSpec((1, sq, tk), lambda b, h, i, j, *_: (b, i, j)),
        ],
        out_specs=pl.BlockSpec((1, G, sq, dv),
                               lambda b, h, i, j, *_: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, sq, 1), jnp.float32),
            pltpu.VMEM((G, sq, 1), jnp.float32),
            pltpu.VMEM((G, sq, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_window_kernel, heads=G, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, s_pad, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_WINDOW_VMEM_BYTES),
        interpret=interpret,
        name=name,
    )(live.astype(jnp.int32).reshape(-1), q_nope, q_rope, k_nope, k_rope, v,
      keep.astype(q_nope.dtype))
    return out[:, :, :S]


def _attention_kernel(bt_ref, run_ref, sl_ref, ck_ref, layer_ref,  # prefetch
                      q_ref, cur_ref,          # [1, H, W], [1, 1, W]
                      keep_ref,                # [1, stages, P * block] int32
                      pool_hbm,                # [L, N, block, W] (ANY/HBM)
                      out_ref,                 # [1, H, value_dim]
                      tile, sem,
                      *, max_blocks: int, pages: int, block: int, group: int,
                      value_dim: int, scale: float):
    b = pl.program_id(0)
    rows = pages * block
    q = q_ref[0]                                      # [H, W]
    H = q.shape[0]
    cached_len = sl_ref[b] - 1                        # rows valid in pages
    n_pages = pl.cdiv(cached_len, block)
    n_stages = pl.cdiv(n_pages, pages)
    layer = layer_ref[0]

    _start, _wait = stage_fetch(
        bt_ref, run_ref, pool_hbm, tile, sem, lane=b, layer=layer,
        n_pages=n_pages, max_blocks=max_blocks, group=group, zero_rest=True)

    @pl.when(n_stages > 0)
    def _prologue():
        _start(0, 0)

    # The current token competes with the cached rows: selected, the softmax
    # starts from it; not selected, from nothing.
    cur = cur_ref[0].astype(jnp.float32)              # [1, W]
    cur_in = ck_ref[b] > 0
    m0 = jnp.sum(q.astype(jnp.float32) * cur, axis=-1, keepdims=True) * scale
    carry = (jnp.where(cur_in, m0, NEG_INF),
             jnp.where(cur_in, jnp.ones((H, 1), jnp.float32), 0.0),
             jnp.where(cur_in, jnp.broadcast_to(cur[:, :value_dim],
                                                (H, value_dim)), 0.0))

    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)

    def stage_body(s, carry):
        m, l, acc = carry
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < n_stages)
        def _prefetch_next():
            _start(s + 1, 1 - slot)

        _wait(s, slot)
        logits = jax.lax.dot_general(
            q, tile[slot].reshape(rows, -1), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, rows]
        seen = ((keep_ref[0, pl.ds(s, 1), :] > 0)
                & (col < cached_len - s * rows))             # [1, rows]
        logits = jnp.where(seen, logits, NEG_INF)
        new_m = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(logits - new_m), 0.0)
        corr = jnp.exp(m - new_m)
        return (new_m, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + jnp.dot(p.astype(tile.dtype),
                                     tile[slot, :, :, :value_dim].reshape(
                                         rows, value_dim),
                                     preferred_element_type=jnp.float32))

    _, l, acc = jax.lax.fori_loop(0, n_stages, stage_body, carry)
    out_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("value_dim", "scale", "interpret"))
def sparse_latent_paged_decode_attention_pallas(
    q: jnp.ndarray,             # [B, H, Dk]
    pages: jnp.ndarray,         # [L, N, block, W] — every layer's latent pool
    layer: jnp.ndarray,         # int32 scalar
    block_tables: jnp.ndarray,  # [B, maxB] int32
    seq_lens: jnp.ndarray,      # [B] int32 (incl. current token)
    cur_row: jnp.ndarray,       # [B, Dk]
    keep: jnp.ndarray,          # [B, maxB * block] bool
    cur_keep: jnp.ndarray,      # [B] bool
    *,
    value_dim: int,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """ops/sparse_attention.sparse_latent_paged_decode_attention, as a
    kernel."""
    B, H, Dk = q.shape
    _, _, block, W = pages.shape
    maxB = block_tables.shape[1]
    n_pages = pages_per_stage(block, W, pages.dtype.itemsize, maxB)
    group = run_pages(n_pages)
    rows = n_pages * block
    stages = -(-maxB // n_pages)
    pad = [(0, 0)] * 2 + [(0, W - Dk)]
    q = jnp.pad(q, pad).astype(pages.dtype)
    cur = jnp.pad(cur_row[:, None], pad).astype(pages.dtype)
    keep = jnp.pad(keep.astype(jnp.int32),
                   ((0, 0), (0, stages * rows - keep.shape[1]))
                   ).reshape(B, stages, rows)

    kernel = functools.partial(
        _attention_kernel, max_blocks=maxB, pages=n_pages, block=block,
        group=group, value_dim=value_dim, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, stages, rows), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, value_dim), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n_pages, block, W), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        interpret=interpret,
        name="dsa_paged_decode_attention",
    )(block_tables.reshape(-1),
      table_runs(block_tables, seq_lens, block, group).reshape(-1), seq_lens,
      cur_keep.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, cur, keep, pages)
