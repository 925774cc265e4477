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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged_attention import NEG_INF, STAGE_VMEM_BYTES


def pages_per_stage(block: int, width: int, itemsize: int,
                    table_width: int) -> int:
    """P: the largest power of two whose two tile slots and the f32 logits
    and probabilities beside them fit STAGE_VMEM_BYTES; at most the table's
    width, at least one page."""
    fit = STAGE_VMEM_BYTES // (block * width * (2 * itemsize + 4))
    p = max(1, min(fit, table_width))
    return 1 << (p.bit_length() - 1)


def _kernel(bt_ref, sl_ref, layer_ref,   # scalar prefetch: [B*maxB], [B], [1]
            q_ref, cur_ref,              # [1, H, W], [1, 1, W]
            pool_hbm,                    # [L, N, block, W] (ANY/HBM)
            out_ref,                     # [1, H, value_dim]
            tile, sem,
            *, max_blocks: int, pages: int, block: int, value_dim: int,
            scale: float):
    b = pl.program_id(0)
    rows = pages * block
    q = q_ref[0]                                      # [H, W]
    H = q.shape[0]
    cached_len = sl_ref[b] - 1                        # rows valid in pages
    n_pages = pl.cdiv(cached_len, block)
    n_stages = pl.cdiv(n_pages, pages)
    layer = layer_ref[0]

    def _rows(i):
        return pl.ds(pl.multiple_of(i * block, block), block)

    def _each_page(s, slot, do):
        """`do(copy)` for each page of stage `s` the lane holds; a loop, not
        an unroll (the engine traces this body for every decode bucket)."""
        def page(i, carry):
            blk = bt_ref[b * max_blocks + s * pages + i]
            do(pltpu.make_async_copy(pool_hbm.at[layer, blk],
                                     tile.at[slot, _rows(i)], sem.at[slot]))
            return carry

        live = jnp.minimum(pages, n_pages - s * pages)
        jax.lax.fori_loop(0, live, page, 0)
        return live

    def _start(s, slot):
        live = _each_page(s, slot, lambda c: c.start())

        def zero(i, carry):
            # Never fetched, and the rows are values too: 0 x whatever VMEM
            # held must be 0.
            tile[slot, _rows(i)] = jnp.zeros((block, tile.shape[-1]),
                                             tile.dtype)
            return carry

        jax.lax.fori_loop(live, pages, zero, 0)

    def _wait(s, slot):
        _each_page(s, slot, lambda c: c.wait())

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
            q, tile[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, rows]
        logits = jnp.where(col < cached_len - s * rows, logits, NEG_INF)
        new_m = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - new_m)
        corr = jnp.exp(m - new_m)
        return (new_m, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + jnp.dot(p.astype(tile.dtype),
                                     tile[slot, :, :value_dim],
                                     preferred_element_type=jnp.float32))

    _, l, acc = jax.lax.fori_loop(0, n_stages, stage_body, carry)
    out_ref[0] = (acc / l).astype(out_ref.dtype)


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
    pad = [(0, 0)] * 2 + [(0, W - Dk)]
    q = jnp.pad(q, pad).astype(pages.dtype)
    cur = jnp.pad(cur_row[:, None], pad).astype(pages.dtype)

    kernel = functools.partial(
        _kernel, max_blocks=maxB, pages=n_pages, block=block,
        value_dim=value_dim, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, value_dim), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n_pages * block, W), pages.dtype),
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
    )(block_tables.reshape(-1), seq_lens,
      jnp.asarray(layer, jnp.int32).reshape(1), q, cur, pages)
