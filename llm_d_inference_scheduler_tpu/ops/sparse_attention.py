"""Learned sparse attention's parts in plain XLA (DeepSeek-V3.2's: an indexer
scores every cached token, a query attends to the ``k`` rows that score
highest): the indexer's scores, the exact selection, a window's attention in
the expanded latent form and decode attention in the absorbed form, both over
the selected rows. ops/pallas_dsa.py has all but the selection as kernels
with these signatures; these are the CPU's forms and what the kernels are
tested against. The selection has one form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import NEG_INF


def index_scores(q: jnp.ndarray,     # [B, S, Hi, Di] — the indexer's queries
                 w: jnp.ndarray,     # [B, S, Hi] f32 — a weight a head
                 keys: jnp.ndarray,  # [B, T, Di] — the indexer's keys
                 ) -> jnp.ndarray:
    """``I[b, s, t] = sum_j w[b, s, j] relu(q[b, s, j] . keys[b, t])``, f32
    [B, S, T]. Products in the operands' dtype with f32 accumulation. This
    form carries [B, S, Hi, T] through memory; the kernel does not."""
    per_head = jnp.einsum("bshd,btd->bsht", q, keys.astype(q.dtype),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bsht,bsh->bst", jax.nn.relu(per_head),
                      w.astype(jnp.float32))


def _ordered_bits(x: jnp.ndarray) -> jnp.ndarray:
    """f32 -> uint32 whose unsigned order is the floats' order (-0.0 and 0.0
    made one value first, as a comparison of floats has them)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 0, bits | jnp.uint32(1 << 31), ~bits)


def select_top(scores: jnp.ndarray,  # [..., T] f32
               valid: jnp.ndarray,   # [..., T] bool — rows a query may see
               k: int) -> jnp.ndarray:
    """Which rows a query attends to: the ``min(k, valid rows)`` valid rows
    with the largest scores, ties to the lower position; bool [..., T]. Exact,
    and no sort: the k-th largest value is found bit by bit (32 counts over
    the row: "how many keys are at least this?"), rows above it are in, and
    of the rows equal to it as many of the first as are still missing."""
    keys = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))

    def refine(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(
        0, 32, refine, jnp.zeros(keys.shape[:-1], jnp.uint32))[..., None]
    above = keys > kth
    missing = k - jnp.sum(above, axis=-1, dtype=jnp.int32, keepdims=True)
    level = keys == kth
    first = jnp.cumsum(level, axis=-1, dtype=jnp.int32) <= missing
    return (above | (level & first)) & valid


def masked_window_attention(q_nope: jnp.ndarray,  # [B, H, S, dn]
                            q_rope: jnp.ndarray,  # [B, H, S, dr]
                            k_nope: jnp.ndarray,  # [B, H, T, dn]
                            k_rope: jnp.ndarray,  # [B, T, dr]
                            v: jnp.ndarray,       # [B, H, T, dv]
                            keep: jnp.ndarray,    # [B, S, T] bool
                            *, scale: float) -> jnp.ndarray:
    """A run of queries against rows carried out to their keys and values
    (the expanded latent form; one rotated key part for all heads), the
    softmax over the rows ``keep`` says: [B, H, S, dv] in q_nope.dtype.
    Products in the operands' dtype with f32 accumulation, the softmax in
    f32. The [B, H, S, T] scores are whole here; the kernel's never are."""
    f32 = dict(preferred_element_type=jnp.float32)
    scores = (jnp.einsum("bhsd,bhtd->bhst", q_nope, k_nope, **f32)
              + jnp.einsum("bhsd,btd->bhst", q_rope,
                           k_rope.astype(q_rope.dtype), **f32))
    scores = jnp.where(keep[:, None], scores * scale, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v, **f32).astype(q_nope.dtype)


def sparse_latent_paged_decode_attention(
    q: jnp.ndarray,             # [B, H, Dk]
    pages: jnp.ndarray,         # [L, N_blocks, block, W] — the latent pool
    layer: jnp.ndarray,         # int32 scalar
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    seq_lens: jnp.ndarray,      # [B] int32 — incl. the current token
    cur_row: jnp.ndarray,       # [B, Dk]
    keep: jnp.ndarray,          # [B, max_blocks * block] bool — rows selected
    cur_keep: jnp.ndarray,      # [B] bool — the current token selected
    *,
    value_dim: int,
    scale: float,
) -> jnp.ndarray:
    """ops/attention.latent_paged_decode_attention with the softmax taken
    over the selected rows alone: ``keep`` says which cached rows, and the
    current token competes like any other (``cur_keep``). A lane that
    selected nothing gives zeros. Returns [B, H, value_dim] in q.dtype."""
    B, H, Dk = q.shape
    T = block_tables.shape[1] * pages.shape[2]
    rows = pages[layer, block_tables].reshape(B, T, -1)[..., :Dk]
    rows = jnp.concatenate([rows, cur_row[:, None].astype(rows.dtype)],
                           axis=1).astype(jnp.float32)        # [B, T+1, Dk]
    logits = jnp.einsum("bhd,btd->bht", q.astype(jnp.float32), rows) * scale
    seen = jnp.concatenate(
        [keep & (jnp.arange(T)[None, :] < (seq_lens - 1)[:, None]),
         cur_keep[:, None]], axis=1)[:, None, :]
    logits = jnp.where(seen, logits, NEG_INF)
    probs = jnp.where(seen, jnp.exp(
        logits - jnp.max(logits, axis=-1, keepdims=True)), 0.0)
    probs = probs / jnp.maximum(jnp.sum(probs, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bht,btd->bhd", probs, rows[..., :value_dim])
    return out.astype(q.dtype)
