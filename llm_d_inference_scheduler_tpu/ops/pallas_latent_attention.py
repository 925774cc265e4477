"""Pallas TPU kernel: paged decode attention over a latent (MLA) page pool.

The absorbed form of latent attention (models/mla.py) leaves decode with one
cached row a token a layer, shared by every head, that is key and value at
once: the query (its no-rope part carried into the latent space, then its
rotated part) meets all of the row's columns for the score, and the
probabilities weigh the row's leading ``value_dim`` columns. So this kernel is
ops/pallas_paged_attention.py's walk — a program a lane, the lane's block
table prefetched, stages of P pages double-buffered, a flash-style running
softmax, the current token's row absorbed first from its own operand — with
one tile where that one has two, and no head grouping: every query head reads
every row. A stage's tile is read from HBM once and used twice.

The pool is every layer's, stacked, [L, N, block, W], left in HBM, and a DMA
addresses (layer, page): a layer's slice of it would be copied for the custom
call (PR 26). W is the row padded to whole lanes (kvcache/pages.py says by how
much); the query arrives padded with zeros to match, so the padding adds
nothing to a score, and the value's columns stop before it. The tile goes to
the MXU in the pool's dtype with f32 accumulation; the softmax state and the
logits are f32, and the probabilities are rounded to the pool's dtype for the
second product, as the values they weigh are.

**A stage's fetch** (:func:`stage_fetch`; ops/pallas_dsa.py's two walks and
the K/V family's, ops/pallas_paged_attention.py, call it too: a tile is
``[2, P, block, ...]`` whatever a page's trailing dims are, a latent row or
KV heads x head_dim). A copy's issue and its wait cost the scalar core some
38 ns a page whatever the page holds (4 KB or 20 KB; PERF.md section 6,
PR 42), in the same instruction stream as the stage's matmuls, so a stage is fetched in
groups of ``RUN_PAGES`` table entries: where a group names adjacent blocks
``b, b+1, ...`` and lies whole inside the lane's cached pages it is ONE copy
of ``pool[layer, b : b + R]``; any other group is a copy a page, as every
page was. Which groups are runs is read off the block table in the jitted
wrapper (:func:`table_runs`) and prefetched beside it; the allocator hands a
request its blocks in ascending order, and a window's pages in aligned
stretches of ``RUN_PAGES``, so that most are (engine/blocks.py). And a full
stage is waited for once, not a copy at a time.

**A window of the context** (:func:`swa_latent_decode_attention_pallas`, the
op ``swa_latent_decode_attention``): layers that attend to the last ``window``
tokens keep their rows in a pool of their own width under a table of their own
(kvcache/pages.py), and a lane's walk starts at the page of its first visible
row, ``max(0, t - (window - 1))``, not at page 0. The wrapper cuts the lane's
walk down to the pages the window reaches (ops/attention.window_cut), from a
multiple of ``RUN_PAGES`` entries so that the walk's groups are the window
pool's stretches, and the kernel is the walk above from that entry of the
lane's table row on, with one more mask for the rows that lie before the
window. (The table is not gathered down to the cut: an entry at a time that
gather was 2% of a long-context decode step.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import (NEG_INF, window_cut, window_pages,  # noqa: F401
                        window_table)

# What a stage's tiles may take of VMEM, here and in the K/V family's walks
# (ops/pallas_paged_attention.py: K and V, two slots each, and a stage's
# rows a KV head apart, in the pool's dtype). A quarter of the 16 MiB a
# kernel may hold by default; the logits and what the compiler keeps besides
# are a tenth of the tiles.
STAGE_VMEM_BYTES = 4 * 1024 * 1024


def pages_per_stage(block: int, width: int, itemsize: int,
                    table_width: int) -> int:
    """P: the largest power of two whose two tile slots and the f32 logits
    and probabilities beside them fit STAGE_VMEM_BYTES; at most the table's
    width, at least one page."""
    fit = STAGE_VMEM_BYTES // (block * width * (2 * itemsize + 4))
    p = max(1, min(fit, table_width))
    return 1 << (p.bit_length() - 1)


# Table entries a group (R): a power of two, so that it divides every stage.
# Chosen on the chip from 4 / 8 / 16 / 32 (scripts/microbench_decode.py
# --latent; PERF.md section 6, PR 42): on tables that are all runs 16 is a
# little ahead, on the tables an allocator's churn leaves 8 is.
RUN_PAGES = 8


def run_pages(pages: int) -> int:
    """R for a stage of ``pages`` pages: RUN_PAGES, at most the stage."""
    return min(RUN_PAGES, pages)


def table_runs(block_tables: jnp.ndarray, seq_lens: jnp.ndarray, block: int,
               group: int) -> jnp.ndarray:
    """[B, ceil(maxB / group)] int32: 1 where the aligned group of ``group``
    table entries names adjacent blocks in ascending order AND every page of
    it holds cached rows of the lane (``seq_lens`` counts the current token,
    which is not cached): nothing is fetched that a copy a page would not
    fetch."""
    B, width = block_tables.shape
    n = -(-width // group)
    table = jnp.pad(block_tables, ((0, 0), (0, n * group - width)),
                    constant_values=-1).reshape(B, n, group)
    adjacent = jnp.all(
        table == table[..., :1] + jnp.arange(group, dtype=table.dtype),
        axis=-1)
    n_pages = -(-(seq_lens - 1) // block)
    inside = jnp.arange(1, n + 1) * group <= n_pages[:, None]
    return (adjacent & inside).astype(jnp.int32)


def stage_fetch(bt_ref, run_ref, pool_hbm, tile, sem, *, lane, layer, n_pages,
                max_blocks: int, group: int, zero_rest: bool, first=0,
                also=()):
    """``start(s, slot)`` and ``wait(s, slot)`` for stage ``s`` of ``lane``'s
    pages, counted from entry ``first`` of its table row (a multiple of
    ``group``; a window's walk starts inside the row): the stage's live
    pages of ``pool_hbm[layer]`` into ``tile[slot]`` ([P, block, ...]: a
    page's trailing dims are the pool's, a latent row or KV heads x
    head_dim), a group of ``group`` table entries at a time, one copy
    where ``run_ref`` (:func:`table_runs`, flattened) says the group is a
    run, a copy a page where it does not and past the stage's last whole
    group. With ``zero_rest`` the start also zeroes the stage's pages past
    the lane's last (never fetched, and where rows are values too, 0 x
    whatever VMEM held must be 0). ``also``: more ``(pool_hbm, tile, sem,
    zero_rest)`` under the same table (V beside K), each into its own tile
    on its own semaphore in the same pass: the table and the flags are read
    once, whatever the pools. A DMA semaphore counts bytes, so the wait
    is for a region's bytes however many copies brought them: the whole slot
    in one wait where the stage is full, a group and then a page at a time
    in a lane's last stage. Loops, not unrolls: the engine traces a kernel's
    body for every decode bucket. A pool may be ``(flag, pool, other)``: two
    pools of one page shape, read from ``other`` where the traced ``flag`` is
    set (one kernel for the layers of both kinds of a K/V model:
    ops/pallas_paged_attention.kv_window_prefill_attention)."""
    pools = ((pool_hbm, tile, sem, zero_rest), *also)
    pages = tile.shape[1]
    groups = -(-max_blocks // group)

    def live_pages(s):
        return jnp.minimum(pages, n_pages - s * pages)

    def start(s, slot):
        live = live_pages(s)

        def fetch(blk, at):
            """``at`` of every tile's slot from block(s) ``blk`` on."""
            for hbm, to, done, _ in pools:
                def copy(src, to=to, done=done):
                    pltpu.make_async_copy(src.at[layer, blk], to.at[slot, at],
                                          done.at[slot]).start()

                if isinstance(hbm, tuple):   # (flag, pool, the pool if set)
                    jax.lax.cond(hbm[0], lambda: copy(hbm[2]),
                                 lambda: copy(hbm[1]))
                else:
                    copy(hbm)

        def page(i, carry):
            fetch(bt_ref[lane * max_blocks + first + s * pages + i], i)
            return carry

        def of_group(g, carry):
            entry = first + s * pages + g * group

            def run():
                fetch(pl.ds(bt_ref[lane * max_blocks + entry], group),
                      pl.ds(g * group, group))

            def split():
                jax.lax.fori_loop(g * group, (g + 1) * group, page, 0)

            jax.lax.cond(run_ref[lane * groups + entry // group] > 0,
                         run, split)
            return carry

        whole = live // group
        jax.lax.fori_loop(0, whole, of_group, 0)
        jax.lax.fori_loop(whole * group, live, page, 0)

        def zero(i, carry):
            for _, to, _, rest in pools:
                if rest:
                    to[slot, i] = jnp.zeros(to.shape[2:], to.dtype)
            return carry

        if any(rest for *_, rest in pools):
            jax.lax.fori_loop(live, pages, zero, 0)

    def wait(s, slot):
        live = live_pages(s)

        def arrived(at):
            for _, to, done, _ in pools:
                region = to.at[slot] if at is None else to.at[slot, at]
                pltpu.make_async_copy(region, region, done.at[slot]).wait()

        def by_parts():
            def of_group(g, carry):
                arrived(pl.ds(g * group, group))
                return carry

            def page(i, carry):
                arrived(i)
                return carry

            whole = live // group
            jax.lax.fori_loop(0, whole, of_group, 0)
            jax.lax.fori_loop(whole * group, live, page, 0)

        jax.lax.cond(live == pages, lambda: arrived(None), by_parts)

    return start, wait


def _kernel(bt_ref, run_ref, sl_ref, layer_ref,  # scalar prefetch: [B*maxB],
            #                                      [B*maxB/R], [B], [1]
            q_ref, cur_ref,              # [1, H, W], [1, 1, W]
            pool_hbm,                    # [L, N, block, W] (ANY/HBM)
            out_ref,                     # [1, H, value_dim]
            tile, sem,
            *, max_blocks: int, pages: int, block: int, group: int,
            value_dim: int, scale: float, skip_ref=None, first_ref=None):
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
        n_pages=n_pages, max_blocks=max_blocks, group=group, zero_rest=True,
        first=0 if first_ref is None else first_ref[b])

    @pl.when(n_stages > 0)
    def _prologue():
        _start(0, 0)

    # The current token's row is always visible: the softmax starts from it,
    # while the first stage's copies are in flight.
    cur = cur_ref[0].astype(jnp.float32)              # [1, W]
    m0 = jnp.sum(q.astype(jnp.float32) * cur, axis=-1, keepdims=True) * scale
    carry = (m0, jnp.ones((H, 1), jnp.float32),
             jnp.broadcast_to(cur[:, :value_dim], (H, value_dim)))

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
        seen = col < cached_len - s * rows
        if skip_ref is not None:
            # The first page's rows that lie before the lane's window.
            seen = seen & (col >= skip_ref[b] - s * rows)
        logits = jnp.where(seen, logits, NEG_INF)
        new_m = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - new_m)
        corr = jnp.exp(m - new_m)
        return (new_m, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + jnp.dot(p.astype(tile.dtype),
                                     tile[slot, :, :, :value_dim].reshape(
                                         rows, value_dim),
                                     preferred_element_type=jnp.float32))

    _, l, acc = jax.lax.fori_loop(0, n_stages, stage_body, carry)
    out_ref[0] = (acc / l).astype(out_ref.dtype)


def _window_kernel(bt_ref, run_ref, sl_ref, skip_ref, first_ref, layer_ref,
                   *refs, **kw):
    """:func:`_kernel` over the pages a lane's window reaches
    (ops/attention.window_cut): the walk starts at entry ``first_ref`` [B] of
    the lane's table row, ``sl_ref`` counts from that page, ``skip_ref`` [B]
    is how many rows from there lie before the window."""
    _kernel(bt_ref, run_ref, sl_ref, layer_ref, *refs, skip_ref=skip_ref,
            first_ref=first_ref, **kw)


@functools.partial(jax.jit,
                   static_argnames=("value_dim", "scale", "interpret"))
def latent_paged_decode_attention_pallas(
    q: jnp.ndarray,             # [B, H, Dk]
    pages: jnp.ndarray,         # [L, N, block, W] — every layer's latent pool
    layer: jnp.ndarray,         # int32 scalar
    block_tables: jnp.ndarray,  # [B, maxB] int32
    seq_lens: jnp.ndarray,      # [B] int32 (incl. current token)
    cur_row: jnp.ndarray,       # [B, Dk]
    *,
    value_dim: int,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """ops/attention.latent_paged_decode_attention, as a kernel."""
    B, H, Dk = q.shape
    _, _, block, W = pages.shape
    maxB = block_tables.shape[1]
    n_pages = pages_per_stage(block, W, pages.dtype.itemsize, maxB)
    group = run_pages(n_pages)
    pad = [(0, 0)] * 2 + [(0, W - Dk)]
    q = jnp.pad(q, pad).astype(pages.dtype)
    cur = jnp.pad(cur_row[:, None], pad).astype(pages.dtype)

    kernel = functools.partial(
        _kernel, max_blocks=maxB, pages=n_pages, block=block, group=group,
        value_dim=value_dim, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, W), lambda b, *_: (b, 0, 0)),
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
        # The op's name in a device trace, for whoever reduces one.
        name="mla_paged_decode_attention",
    )(block_tables.reshape(-1),
      table_runs(block_tables, seq_lens, block, group).reshape(-1), seq_lens,
      jnp.asarray(layer, jnp.int32).reshape(1), q, cur, pages)


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "window",
                                             "interpret"))
def swa_latent_decode_attention_pallas(
    q: jnp.ndarray,             # [B, H, Dk]
    pages: jnp.ndarray,         # [Lw, N, block, W] — the window layers' pool
    layer: jnp.ndarray,         # int32 scalar
    block_tables: jnp.ndarray,  # [B, maxB] int32, by logical page
    seq_lens: jnp.ndarray,      # [B] int32 (incl. current token)
    cur_row: jnp.ndarray,       # [B, Dk]
    *,
    value_dim: int,
    scale: float,
    window: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """ops/attention.swa_latent_decode_attention, as a kernel: a query sees
    its own row and the ``window - 1`` cached before it."""
    B, H, Dk = q.shape
    _, _, block, W = pages.shape
    maxB = block_tables.shape[1]
    first, lens, skip = window_cut(seq_lens, block, window, align=RUN_PAGES)
    # A stage as the walk's own length allows, not the whole table's.
    n_pages = pages_per_stage(
        block, W, pages.dtype.itemsize,
        min(maxB, window_pages(block, window, align=RUN_PAGES)))
    group = run_pages(n_pages)
    pad = [(0, 0)] * 2 + [(0, W - Dk)]
    q = jnp.pad(q, pad).astype(pages.dtype)
    cur = jnp.pad(cur_row[:, None], pad).astype(pages.dtype)

    kernel = functools.partial(
        _window_kernel, max_blocks=maxB, pages=n_pages, block=block,
        group=group, value_dim=value_dim, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, value_dim), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n_pages, block, W), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # The table whole, as the full layers' walks take theirs: the kernel
    # reads it from ``first`` on, and its groups of entries are the table's
    # own aligned groups (``first`` is a multiple of RUN_PAGES).
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        interpret=interpret,
        name="swa_latent_decode_attention",
    )(block_tables.reshape(-1),
      table_runs(block_tables, seq_lens, block, group).reshape(-1), lens,
      skip, first, jnp.asarray(layer, jnp.int32).reshape(1), q, cur, pages)
