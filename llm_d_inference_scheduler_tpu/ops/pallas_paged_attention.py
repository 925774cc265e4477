"""Pallas TPU kernel: paged decode attention.

Replaces the XLA gather formulation in ops/attention.py on the decode hot
path. The XLA version materialises [B, max_blocks*block] KV rows in registers
via a gather — O(max_context) HBM traffic per sequence regardless of true
length. This kernel walks each sequence's block table (scalar-prefetched so
indices are known before the body runs), DMAs only the blocks that exist
(ceil(seq_len/block) of them), and keeps a flash-style running softmax in
VMEM. Pattern follows the ragged/paged attention design used by TPU serving
stacks (PAPERS.md: Ragged Paged Attention, arXiv 2604.15464).

Grid: one program per batch row. Per block: async HBM→VMEM copies of the
K and V pages (double-buffered: page j+1's DMA is in flight while page j is
computed), then per-KV-head-group MXU matmuls with f32 accumulation.
The current token's K/V arrives as a separate operand (the engine scatters it
into the pages after the layer scan — see models/llama.py decode_step).

The pages arrive as the engine holds them: every layer's pool stacked,
[L, N, block, Hkv, D], left in HBM, with the layer as a third prefetched
scalar; each DMA addresses (layer, page). XLA cannot fuse a slice into a
custom call's operand, so a kernel handed one layer's pool out of the stack
is handed a copy of it (2 x 67 MB a layer at Qwen3-4B, 4.8 GB a decode
step). A caller with one layer's pool passes ``pool[None]`` and layer 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, sl_ref, layer_ref,  # scalar prefetch: [B*maxB], [B], [1]
            q_ref, cur_k_ref, cur_v_ref,  # VMEM blocks per program
            k_hbm, v_hbm,              # stacked page arrays (ANY/HBM)
            out_ref,                   # [1, H, D]
            k_scratch, v_scratch, sem_k, sem_v,
            *, max_blocks: int, block: int, n_kv: int, q_per_kv: int,
            head_dim: int):
    b = pl.program_id(0)
    H = n_kv * q_per_kv
    scale = 1.0 / (head_dim ** 0.5)

    q = q_ref[0].astype(jnp.float32) * scale          # [H, D]
    q = q.reshape(n_kv, q_per_kv, head_dim)           # [G, qpk, D]
    cached_len = sl_ref[b] - 1                        # rows valid in pages
    layer = layer_ref[0]

    m0 = jnp.full((n_kv, q_per_kv, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_kv, q_per_kv, 1), jnp.float32)
    acc0 = jnp.zeros((n_kv, q_per_kv, head_dim), jnp.float32)

    # Double-buffered page pipeline: page j+1's HBM→VMEM DMA is in flight
    # while page j is computed, so the grid's B sequential programs pay DMA
    # latency once per program instead of once per page (the serial
    # start/wait version was the decode wall at large batch: B × pages ×
    # layers blocking latencies per step).
    def _copies(j, slot):
        blk = bt_ref[b * max_blocks + j]
        return (pltpu.make_async_copy(k_hbm.at[layer, blk], k_scratch.at[slot],
                                      sem_k.at[slot]),
                pltpu.make_async_copy(v_hbm.at[layer, blk], v_scratch.at[slot],
                                      sem_v.at[slot]))

    @pl.when(0 < cached_len)
    def _prologue():
        ck, cv = _copies(0, 0)
        ck.start()
        cv.start()

    def block_body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, 2)

        @pl.when((j + 1) * block < cached_len)
        def _prefetch_next():
            ck, cv = _copies(j + 1, jax.lax.rem(j + 1, 2))
            ck.start()
            cv.start()

        def compute(m, l, acc):
            ck, cv = _copies(j, slot)
            ck.wait()
            cv.wait()
            k = k_scratch[slot].astype(jnp.float32)    # [bs, G, D]
            v = v_scratch[slot].astype(jnp.float32)
            pos = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)               # [1, bs]
            valid = pos < cached_len                    # [1, bs]
            # Static unroll over KV-head groups, rebuilt with stacks (no
            # .at[].set — Mosaic has no scatter lowering).
            ms, ls, accs = [], [], []
            for g in range(n_kv):
                logits = jnp.dot(q[g], k[:, g, :].T,
                                 preferred_element_type=jnp.float32)  # [qpk, bs]
                logits = jnp.where(valid, logits, NEG_INF)
                blk_max = jnp.max(logits, axis=-1, keepdims=True)
                new_m = jnp.maximum(m[g], blk_max)
                p = jnp.exp(logits - new_m) * valid     # re-mask fully-masked rows
                corr = jnp.exp(m[g] - new_m)
                ls.append(l[g] * corr + jnp.sum(p, axis=-1, keepdims=True))
                accs.append(acc[g] * corr + jnp.dot(
                    p, v[:, g, :], preferred_element_type=jnp.float32))
                ms.append(new_m)
            return jnp.stack(ms), jnp.stack(ls), jnp.stack(accs)

        return jax.lax.cond(j * block < cached_len,
                            lambda: compute(m, l, acc),
                            lambda: (m, l, acc))

    m, l, acc = jax.lax.fori_loop(0, max_blocks, block_body, (m0, l0, acc0))

    # Current token's KV: always-visible extra column.
    cur_k = cur_k_ref[0].astype(jnp.float32)          # [G, D]
    cur_v = cur_v_ref[0].astype(jnp.float32)
    ls, accs = [], []
    for g in range(n_kv):
        logits = jnp.dot(q[g], cur_k[g][:, None],
                         preferred_element_type=jnp.float32)  # [qpk, 1]
        new_m = jnp.maximum(m[g], logits)
        p = jnp.exp(logits - new_m)
        corr = jnp.exp(m[g] - new_m)
        ls.append(l[g] * corr + p)
        accs.append(acc[g] * corr + p * cur_v[g][None, :])
    l = jnp.stack(ls)
    acc = jnp.stack(accs)

    out = acc / l                                      # [G, qpk, D]
    out_ref[0] = out.reshape(H, head_dim).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jnp.ndarray,            # [B, H, D]
    k_pages: jnp.ndarray,      # [L, N, block, Hkv, D] — every layer's pool
    v_pages: jnp.ndarray,
    layer: jnp.ndarray,         # int32 scalar — the layer whose pool is read
    block_tables: jnp.ndarray,  # [B, maxB] int32
    seq_lens: jnp.ndarray,      # [B] int32 (incl. current token)
    cur_k: jnp.ndarray,         # [B, Hkv, D]
    cur_v: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, D = q.shape
    _, _, block, n_kv, _ = k_pages.shape
    maxB = block_tables.shape[1]
    q_per_kv = H // n_kv

    kernel = functools.partial(
        _kernel, max_blocks=maxB, block=block, n_kv=n_kv,
        q_per_kv=q_per_kv, head_dim=D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block, n_kv, D), k_pages.dtype),
            pltpu.VMEM((2, block, n_kv, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(block_tables.reshape(-1), seq_lens,
      jnp.asarray(layer, jnp.int32).reshape(1),
      q, cur_k, cur_v, k_pages, v_pages)
