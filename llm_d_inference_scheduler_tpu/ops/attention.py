"""Attention ops for the TPU engine.

Two shapes of attention are needed by the serving stack:

- ``causal_attention``: full-sequence causal attention used by prefill and by
  the training/dry-run path. Plain XLA einsum formulation — XLA fuses the
  softmax chain and tiles the matmuls onto the MXU; a Pallas flash kernel can
  replace it behind the same signature.
- ``paged_decode_attention``: one-token decode against a paged KV cache
  (block-table gather), the JetStream/vLLM-style layout that makes continuous
  batching possible without reshuffling KV state.

All softmax math accumulates in f32 regardless of input dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(x: jnp.ndarray, q_per_kv: int) -> jnp.ndarray:
    """[..., n_kv, d] -> [..., n_kv * q_per_kv, d] (GQA head broadcast)."""
    if q_per_kv == 1:
        return x
    return jnp.repeat(x, q_per_kv, axis=-2)


def causal_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    *,
    q_positions: jnp.ndarray | None = None,  # [B, S] global positions of q rows
    kv_positions: jnp.ndarray | None = None,  # [B, T]
    kv_valid: jnp.ndarray | None = None,  # [B, T] bool — padding mask for kv
) -> jnp.ndarray:
    """Causal attention; returns [B, S, H, D] in q.dtype.

    When positions are omitted, q and kv are assumed aligned ([B, S] == [B, T])
    with standard lower-triangular causality.
    """
    B, S, H, D = q.shape
    T = k.shape[1]
    q_per_kv = H // k.shape[2]
    k = _repeat_kv(k, q_per_kv)
    v = _repeat_kv(v, q_per_kv)

    scale = 1.0 / (D ** 0.5)
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), k.astype(jnp.float32)) * scale

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    mask = q_positions[:, None, :, None] >= kv_positions[:, None, None, :]  # [B,1,S,T]
    if kv_valid is not None:
        mask = jnp.logical_and(mask, kv_valid[:, None, None, :])
    logits = jnp.where(mask, logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# Queries a step of :func:`banded_attention`: the scores it holds at once are
# [heads, QUERY_BLOCK, rows] in f32 (186 MB at 28 heads over 13k rows).
QUERY_BLOCK = 128


def banded_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    *,
    q_positions: jnp.ndarray,   # [B, S]
    kv_positions: jnp.ndarray,  # [B, T]
    kv_valid: jnp.ndarray | None = None,  # [B, T] bool
    window: int | jnp.ndarray | None = None,
    q_block: int = QUERY_BLOCK,
) -> jnp.ndarray:
    """:func:`causal_attention` for a model some of whose layers attend to a
    window and whose contexts are long: with ``window`` (a number, or a traced
    scalar where a scan decides a layer) a query at t sees s with ``0 <= t -
    s < window``; the queries go ``q_block`` at a time (a
    loop on the device where S is more, S padded to whole blocks), so the
    scores are never whole in memory ([H, S, T] in f32: 1.5 GB for 1,024
    queries over 13k rows at 28 heads); and a KV head's rows are not repeated for its query heads.
    Products in the operands' dtype with f32 accumulation, the softmax in
    f32. Returns [B, S, H, D] in q.dtype."""
    B, S, H, D = q.shape
    n_kv = k.shape[2]
    scale = 1.0 / (D ** 0.5)
    f32 = dict(preferred_element_type=jnp.float32)

    def block(qb, pos):                        # [B, s, H, D], [B, s]
        s = qb.shape[1]
        grouped = qb.reshape(B, s, n_kv, H // n_kv, D)
        logits = jnp.einsum("bsngd,btnd->bngst", grouped, k, **f32) * scale
        back = pos[:, :, None] - kv_positions[:, None, :]        # [B, s, T]
        mask = back >= 0
        if window is not None:
            mask = mask & (back < window)
        if kv_valid is not None:
            mask = mask & kv_valid[:, None, :]
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bngst,btnd->bsngd", probs, v, **f32)
        return out.reshape(B, s, H, D).astype(q.dtype)

    if S <= q_block:
        return block(q, q_positions)
    n = -(-S // q_block)
    spare = n * q_block - S     # queries that fill the last block: they
    if spare:                   # repeat the last position, and are dropped
        q = jnp.pad(q, ((0, 0), (0, spare), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, spare)), mode="edge")
    out = jax.lax.map(
        lambda x: block(*x),
        (jnp.moveaxis(q.reshape(B, n, q_block, H, D), 1, 0),
         jnp.moveaxis(q_positions.reshape(B, n, q_block), 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, n * q_block, H, D)
    return out[:, :S] if spare else out


def paged_decode_attention(
    q: jnp.ndarray,            # [B, H, D] — one new token per sequence
    k_pages: jnp.ndarray,      # [L, N_blocks, block, Hkv, D] — every layer's pool
    v_pages: jnp.ndarray,      # [L, N_blocks, block, Hkv, D]
    layer: jnp.ndarray,         # int32 scalar — the layer whose pool is read
    block_tables: jnp.ndarray,  # [B, max_blocks] int32 — physical block ids
    seq_lens: jnp.ndarray,      # [B] int32 — tokens valid in cache (incl. current)
    cur_k: jnp.ndarray | None = None,  # [B, Hkv, D] current token's K (not yet in pages)
    cur_v: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Decode-step attention over a paged KV cache; returns [B, H, D].

    When ``cur_k``/``cur_v`` are given, the current token's KV is appended as
    an extra attention column instead of being read from the pages (the engine
    then scatters all layers' current-token KV in one fused write after the
    layer scan). Cache rows at the current position are masked as invalid in
    that mode.

    The pools stay stacked and one gather reads (layer, page), so no layer's
    pool is sliced out first; a caller with one layer's pool passes
    ``pool[None]`` and layer 0. The gather materialises [B, max_blocks*block]
    KV rows; a Pallas kernel with scalar-prefetched block tables replaces this
    on the hot path (ops/pallas_paged_attention.py, same signature).
    """
    B, H, D = q.shape
    block = k_pages.shape[2]
    max_blocks = block_tables.shape[1]
    T = max_blocks * block
    q_per_kv = H // k_pages.shape[3]

    k = k_pages[layer, block_tables].reshape(B, T, -1, D)  # [B, T, Hkv, D]
    v = v_pages[layer, block_tables].reshape(B, T, -1, D)
    cached_valid_len = seq_lens if cur_k is None else seq_lens - 1
    if cur_k is not None:
        k = jnp.concatenate([k, cur_k[:, None]], axis=1)  # [B, T+1, Hkv, D]
        v = jnp.concatenate([v, cur_v[:, None]], axis=1)
    k = _repeat_kv(k, q_per_kv)
    v = _repeat_kv(v, q_per_kv)
    total = k.shape[1]

    scale = 1.0 / (D ** 0.5)
    logits = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    valid = jnp.arange(T)[None, :] < cached_valid_len[:, None]  # [B, T]
    if cur_k is not None:
        valid = jnp.concatenate(
            [valid, jnp.ones((B, 1), bool)], axis=1)  # current token always visible
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def latent_paged_decode_attention(
    q: jnp.ndarray,             # [B, H, Dk] — [q absorbed into the latent | q_rope]
    pages: jnp.ndarray,         # [L, N_blocks, block, W] — every layer's latent pool, W >= Dk
    layer: jnp.ndarray,         # int32 scalar — the layer whose pool is read
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    seq_lens: jnp.ndarray,      # [B] int32 — incl. the current token
    cur_row: jnp.ndarray,       # [B, Dk] — the current token's row, not in the pages yet
    *,
    value_dim: int,             # the row's leading columns that are the value
    scale: float,
) -> jnp.ndarray:
    """Decode-step attention of the absorbed latent form; returns [B, H,
    value_dim] in q.dtype.

    A cached row is one vector for every head, and it is key and value at
    once: all Dk columns against the query give the score, the first
    ``value_dim`` of them (the latent; the rest is the rotated key part) are
    what the probabilities weigh. Columns of a page past Dk are the layout's
    padding and are not read. The current token is an extra, always visible
    column, as in :func:`paged_decode_attention`; the Pallas kernel
    (ops/pallas_latent_attention.py) has the same signature.
    """
    B, H, Dk = q.shape
    block = pages.shape[2]
    T = block_tables.shape[1] * block
    rows = pages[layer, block_tables].reshape(B, T, -1)[..., :Dk]
    rows = jnp.concatenate([rows, cur_row[:, None].astype(rows.dtype)],
                           axis=1).astype(jnp.float32)        # [B, T+1, Dk]
    logits = jnp.einsum("bhd,btd->bht", q.astype(jnp.float32), rows) * scale
    valid = jnp.concatenate(
        [jnp.arange(T)[None, :] < (seq_lens - 1)[:, None],
         jnp.ones((B, 1), bool)], axis=1)
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,btd->bhd", probs, rows[..., :value_dim])
    return out.astype(q.dtype)


def window_pages(block: int, window: int, align: int = 1) -> int:
    """Pages the cached rows of a window can lie across: ``window - 1`` rows
    (the query's own is not cached) from any offset into the first page; and
    ``align - 1`` more ahead of them for a walk that starts at a multiple of
    ``align`` table entries (:func:`window_cut`)."""
    return (window + block - 3) // block + align


def window_cut(seq_lens: jnp.ndarray, block: int, window: int,
               align: int = 1
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Where a lane's walk over its window starts, by logical page: (the
    first table entry of the walk [B], the lane's length counted from that
    page [B], the rows from there that lie before the window [B]) for a query
    at ``seq_lens - 1``. The walk starts at a multiple of ``align`` entries,
    at most ``align - 1`` before the window's first page: a kernel that
    fetches aligned groups of the table as one copy
    (ops/pallas_latent_attention.stage_fetch) then sees the groups as the
    window pool handed them out (engine/blocks.WindowedAllocator, whose
    stretches a lane keeps whole while any row of them is in reach)."""
    first_row = jnp.maximum(seq_lens - window, 0)
    first = first_row // block
    first -= first % align
    return first, seq_lens - first * block, first_row - first * block


def window_table(block_tables: jnp.ndarray, seq_lens: jnp.ndarray,
                 block: int, window: int, align: int = 1
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A lane's table cut to its window (:func:`window_cut`): (the entries of
    the pages that hold the rows a query at ``seq_lens - 1`` sees [B,
    window_pages], the lane's length counted from the first of
    those pages [B], the rows of those pages that lie before the window
    [B]). The Pallas walks read the table from the cut's first entry
    themselves; the gather here is the plain forms'."""
    first, lens, skip = window_cut(seq_lens, block, window, align)
    at = first[:, None] + jnp.arange(window_pages(block, window, align),
                                     dtype=first.dtype)[None, :]
    tables = jnp.take_along_axis(
        block_tables, jnp.minimum(at, block_tables.shape[1] - 1), axis=1)
    return tables, lens, skip


def swa_latent_decode_attention(
    q: jnp.ndarray,             # [B, H, Dk]
    pages: jnp.ndarray,         # [Lw, N_blocks, block, W] — the window layers' pool
    layer: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32, by logical page
    seq_lens: jnp.ndarray,      # [B] int32 — incl. the current token
    cur_row: jnp.ndarray,       # [B, Dk]
    *,
    value_dim: int,
    scale: float,
    window: int,
) -> jnp.ndarray:
    """:func:`latent_paged_decode_attention` for layers that attend to a
    window of the context: the query at ``t = seq_lens - 1`` sees its own row
    and the cached rows s with ``t - s < window``. Only the pages the window
    reaches are gathered (:func:`window_table`): the entries of the table
    before them may name pages the lane gave back."""
    B, H, Dk = q.shape
    block = pages.shape[2]
    tables, lens, skip = window_table(block_tables, seq_lens, block, window)
    T = tables.shape[1] * block
    rows = pages[layer, tables].reshape(B, T, -1)[..., :Dk]
    rows = jnp.concatenate([rows, cur_row[:, None].astype(rows.dtype)],
                           axis=1).astype(jnp.float32)        # [B, T+1, Dk]
    logits = jnp.einsum("bhd,btd->bht", q.astype(jnp.float32), rows) * scale
    col = jnp.arange(T)[None, :]
    valid = jnp.concatenate(
        [(col >= skip[:, None]) & (col < (lens - 1)[:, None]),
         jnp.ones((B, 1), bool)], axis=1)
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,btd->bhd", probs, rows[..., :value_dim])
    return out.astype(q.dtype)


def swa_paged_decode_attention(
    q: jnp.ndarray,             # [B, H, D]
    k_pages: jnp.ndarray,       # [Lw, N_blocks, block, Hkv, D] — the window layers' K pool
    v_pages: jnp.ndarray,
    layer: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32, by logical page
    seq_lens: jnp.ndarray,      # [B] int32 — incl. the current token
    cur_k: jnp.ndarray,         # [B, Hkv, D]
    cur_v: jnp.ndarray,
    *,
    window: int,
) -> jnp.ndarray:
    """:func:`paged_decode_attention` for layers that attend to a window of
    the context, as :func:`swa_latent_decode_attention` is the latent form's:
    the query at ``t = seq_lens - 1`` sees its own K/V and the cached rows s
    with ``t - s < window``, and only the pages the window reaches are
    gathered (:func:`window_table`). The Pallas kernel
    (ops/pallas_paged_attention.py, op ``swa_paged_decode_attention``) has the
    same signature."""
    B, H, D = q.shape
    block = k_pages.shape[2]
    q_per_kv = H // k_pages.shape[3]
    tables, lens, skip = window_table(block_tables, seq_lens, block, window)
    T = tables.shape[1] * block

    def rows(pool, cur):
        got = pool[layer, tables].reshape(B, T, -1, D)
        return _repeat_kv(jnp.concatenate([got, cur[:, None]], axis=1),
                          q_per_kv).astype(jnp.float32)          # [B, T+1, H, D]

    k, v = rows(k_pages, cur_k), rows(v_pages, cur_v)
    logits = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32), k) / (D ** 0.5)
    col = jnp.arange(T)[None, :]
    valid = jnp.concatenate(
        [(col >= skip[:, None]) & (col < (lens - 1)[:, None]),
         jnp.ones((B, 1), bool)], axis=1)
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bht,bthd->bhd", probs, v).astype(q.dtype)
