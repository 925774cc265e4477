"""Pallas TPU kernel: paged decode attention.

Replaces the XLA gather formulation in ops/attention.py on the decode hot
path. The XLA version materialises [B, max_blocks*block] KV rows in registers
via a gather — O(max_context) HBM traffic per sequence regardless of true
length. This kernel walks each sequence's block table (scalar-prefetched so
indices are known before the body runs), DMAs only the blocks that exist
(ceil(seq_len/block) of them), and keeps a flash-style running softmax in
VMEM. Pattern follows the ragged/paged attention design used by TPU serving
stacks (PAPERS.md: Ragged Paged Attention, arXiv 2604.15464).

Grid: one program per batch row. A program works in *stages* of P pages
(P x block tokens). A stage's K pages and V pages are fetched together, each
pool's into a `[P, block, Hkv, D]` VMEM tile of its own, and the next stage's
copies are in flight while this one is computed. The loop runs over the
lane's own stages, cdiv(pages it holds, P), so a short lane pays for neither
the table's width nor a wide stage: pages of its last stage past its length
are not fetched (their V rows are zeroed, their logits masked).

A stage is fetched as the latent family's walks fetch theirs
(ops/pallas_latent_attention.stage_fetch, one function for all five): a
copy's issue costs the scalar core ~38 ns whatever the page holds, against
20-40 ns of bytes a 16-32 KB page of K and V, so the table is taken in groups
of R = ``run_pages(P)`` entries, ONE copy of ``pool[layer, b : b + R]`` where
a group names adjacent ascending blocks inside the lane's cached pages
(``table_runs``, computed in the jitted wrappers and prefetched beside the
table), a copy a page elsewhere, K and V in the same pass over the table, and
a full stage is waited for once a pool.
The allocators hand out blocks so that the runs exist (engine/blocks.py: a
table ascending, a window's pages in aligned stretches of ``RUN_PAGES``).

A stage is computed a KV head apart, in the pool's dtype. The tile lies in
VMEM as the page lies in the pool, row (token t, KV head g), so head g's rows
are every Hkv-th of it: strided loads take them out (:func:`_head_rows`; of
32-bit words, two heads of a 16-bit pool at once) and they are stacked
[Hkv, P*block, D]. Each head's rows meet its own query heads alone, ONE
product over the head axis q[Hkv, q_per_kv, D] . k[Hkv, rows, D]^T and one
p . v, between them one step of every head's running softmax (its state
[Hkv, q_per_kv, 1 | 1 | D] in f32, the stage loop's carry). Both products go
to the MXU in the pool's dtype with f32 accumulation; the scale is applied to
the f32 logits, the mask is the positions alone (the lane's length, a
window's first row), and the probabilities are rounded to the pool's dtype
for the second product, as the values they weigh are: the arithmetic
ops/attention.banded_attention states and the latent walks use. No f32 copy
of a tile is made. The heads are a batch dimension and not a loop of
products: a step of one head's softmax is a chain of latencies (product,
maximum, exponential, sum, product) that nothing of the same head can fill,
and the compiler interleaves the heads' chains only where it sees them side
by side (a `fori_loop` over the heads read 63 ns a 4-head page and 251 an
8-head one where this reads 46 and 113; PERF.md section 6, PR 53).
The current token's K/V arrives as a separate operand (the engine scatters it
into the pages after the layer scan — see models/llama.py decode_step) and is
what the softmax starts from, a KV head against its own query heads, while
the first stage's copies are in flight.

P is not a setting: `pages_per_stage` takes it from the shapes the call is
traced with, as the largest power of two for which the two double-buffered
tiles and the computed stage's rows laid out a KV head apart fit
`STAGE_VMEM_BYTES`, and no more than the table is wide: 32 pages at 4 KV
heads of 128 in bf16, 16 at 8, 64 at 2. (A lane's first stage is fetched with
nothing to compute beside it, so a stage twice as long, which the tiles alone
would fit, read 2-10% slower at every head count: PERF.md section 6, PR 53.)
K and V stay in the pool's dtype in HBM and VMEM; the softmax state and the
logits are f32.

The pages arrive as the engine holds them: every layer's pool stacked,
[L, N, block, Hkv, D], left in HBM, with the layer as a third prefetched
scalar; each DMA addresses (layer, page). XLA cannot fuse a slice into a
custom call's operand, so a kernel handed one layer's pool out of the stack
is handed a copy of it (2 x 67 MB a layer at Qwen3-4B, 4.8 GB a decode
step). A caller with one layer's pool passes ``pool[None]`` and layer 0.

**A window of the context** (:func:`swa_paged_decode_attention_kernel`, the op
``swa_paged_decode_attention`` in a device trace, a name of its own so that a
trace tells the window layers' walks from the others'): layers that attend to
the last ``window`` tokens keep a pool pair of their own under a table of their
own (kvcache/pages.py). The wrapper reckons where in a lane's table row its
window starts (ops/attention.window_cut: ``window_pages`` entries are in
reach, 257 for 4,096 tokens in pages of 16) and the same stages walk the row
from there (``first_ref``), with the rows that lie before the window masked
(``skip_ref``). The walk starts at a multiple of ``RUN_PAGES`` table entries,
up to ``RUN_PAGES - 1`` pages before the window's first, so that the kernel's
groups are the aligned stretches the window pool hands out
(engine/blocks.WindowedAllocator) and not a shifted view of them that holds
no run.

Neither form pads its query heads: 28 heads on 4 KV heads (7 a group, no
multiple of 8) compile for a v5e as they are (tests/test_chip_compile.py).

**Heads narrower than a lane** (``side`` > 1: kvcache/pages.py keeps ``side``
adjacent KV heads of ``D / side`` values side by side as one page row of D =
128, and hands the walk that many fewer, wider "KV heads"): the stages, the
copies and the two products are the same program. A query row arrives zero
outside the lanes of its own KV head, so the row's other heads' keys add
nothing to its logits; the scale is the narrow head's, ``1 / sqrt(D /
side)``; the second product fills all D lanes of a row's accumulator and the
row's own ``D / side`` are taken out of it in VMEM (the first ``q_per_kv /
side`` rows of a group belong to the row's first head, and so on), so the
kernel hands back ``[B, H, D / side]``, the caller's own shape, with no
operation behind it.

**A window of a prompt** (:func:`kv_window_prefill_attention`, the op of that
name): the same stages under a tile of queries, for the windows of a long
prompt that continue what earlier ones cached; described where it stands, at
the end of this module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, window_cut, window_pages
from .pallas_latent_attention import (RUN_PAGES, STAGE_VMEM_BYTES, run_pages,
                                      stage_fetch, table_runs)


def stage_vmem_bytes(pages: int, block: int, n_kv: int, head_dim: int,
                     itemsize: int) -> int:
    """Bytes of VMEM that stages of `pages` pages hold at once: K and V, two
    slots each, and the rows of the stage that is computed laid out a KV
    head apart, a tile of K and of V, all in the pool's dtype."""
    tile = pages * block * n_kv * head_dim
    return (2 * 2 + 2) * tile * itemsize


def pages_per_stage(block: int, n_kv: int, head_dim: int, itemsize: int,
                    table_width: int) -> int:
    """P: the largest power of two whose stage fits STAGE_VMEM_BYTES, at
    most the table's width and at least one page."""
    fit = STAGE_VMEM_BYTES // stage_vmem_bytes(1, block, n_kv, head_dim,
                                               itemsize)
    p = max(1, min(fit, table_width))
    return 1 << (p.bit_length() - 1)


def _kernel(bt_ref, run_ref, sl_ref, layer_ref,  # scalar prefetch: [B*maxB],
            #                                      [B*maxB/R], [B], [1]
            q_ref,                     # [1, H, D], in the pool's dtype
            cur_k_ref, cur_v_ref,      # [1, Hkv, D]
            k_hbm, v_hbm,              # stacked page arrays (ANY/HBM)
            out_ref,                   # [1, H, D]
            k_tile, v_tile, sem_k, sem_v,   # [2, P, block, Hkv, D] each pool
            *more,                     # with ``side`` > 1: a [H, D] f32 scratch
            max_blocks: int, pages: int, block: int, group: int,
            side: int = 1, skip_ref=None, first_ref=None):
    b = pl.program_id(0)
    n_kv, head_dim = cur_k_ref.shape[1:]
    H = q_ref.shape[1]
    q_per_kv = H // n_kv
    rows = pages * block                              # tokens a stage
    scale = 1.0 / ((head_dim // side) ** 0.5)

    # A KV head's query heads apart (nothing is padded: 7 a group as they
    # are), once a program.
    q = q_ref[0].reshape(n_kv, q_per_kv, head_dim)
    cached_len = sl_ref[b] - 1                        # rows valid in pages
    n_pages = pl.cdiv(cached_len, block)
    n_stages = pl.cdiv(n_pages, pages)

    # K and V in one pass over the table, each into its own tile on its own
    # semaphore. K's rows past the lane's last page are masked as logits;
    # V's enter the product, and 0 x whatever VMEM held must be 0.
    _start, _wait = stage_fetch(
        bt_ref, run_ref, k_hbm, k_tile, sem_k, zero_rest=False,
        also=((v_hbm, v_tile, sem_v, True),), lane=b, layer=layer_ref[0],
        n_pages=n_pages, max_blocks=max_blocks, group=group,
        first=0 if first_ref is None else first_ref[b])

    @pl.when(n_stages > 0)
    def _prologue():
        _start(0, 0)

    # The current token's K/V is always visible: every head's softmax starts
    # from it (its own logit the maximum, weight 1), while the first stage's
    # copies are in flight.
    cur_k, cur_v = (ref[0].astype(jnp.float32)[:, None, :]
                    for ref in (cur_k_ref, cur_v_ref))       # [Hkv, 1, D]
    carry = (jnp.sum(q.astype(jnp.float32) * cur_k, axis=-1,
                     keepdims=True) * scale,
             jnp.ones((n_kv, q_per_kv, 1), jnp.float32),
             jnp.broadcast_to(cur_v, (n_kv, q_per_kv, head_dim)))

    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, rows), 2)

    def stage_body(s, carry):
        m, l, acc = carry                      # [Hkv, q_per_kv, 1 | 1 | D]
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < n_stages)
        def _prefetch_next():
            _start(s + 1, 1 - slot)

        _wait(s, slot)
        # The stage's rows a KV head apart, [Hkv, rows, D]: one product over
        # the head axis, and one step of every head's running softmax.
        k = jnp.stack(_head_rows(k_tile, slot, n_kv))
        v = jnp.stack(_head_rows(v_tile, slot, n_kv))
        logits = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, q_per_kv, rows]
        # Row t of a head's is position s * rows + t.
        seen = col < cached_len - s * rows
        if skip_ref is not None:   # the rows that lie before the window
            seen = seen & (col >= skip_ref[b] - s * rows)
        logits = jnp.where(seen, logits, NEG_INF)
        new_m = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - new_m)
        corr = jnp.exp(m - new_m)
        return (new_m, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32))

    _, l, acc = jax.lax.fori_loop(0, n_stages, stage_body, carry)
    out = (acc / l).reshape(H, head_dim)
    if side > 1:
        out = _rows_side_by_side(out, more[0], q_per_kv, side)
    out_ref[0] = out.astype(out_ref.dtype)


def _rows_side_by_side(out, scratch, q_per_kv: int, side: int):
    """out [H, D] f32, row h holding its query head's result in the lanes of
    its own KV head (head ``(h % q_per_kv) // (q_per_kv / side)`` of the
    ``side`` a page row holds, ``D / side`` lanes each) and another head's in
    the others -> [H / side, D]: row j the results of query heads ``side x
    j ... side x j + side - 1`` side by side, ``D / side`` lanes each."""
    H, D = out.shape
    d = D // side
    of = (jax.lax.broadcasted_iota(jnp.int32, (H, D), 0) % q_per_kv
          ) // (q_per_kv // side)
    # Every row's own lanes rotated to the front.
    front = out
    for i in range(1, side):
        front = jnp.where(of == i, pltpu.roll(out, D - i * d, 1), front)
    scratch[...] = front
    lane = jax.lax.broadcasted_iota(jnp.int32, (H // side, D), 1)
    rows = scratch[pl.ds(0, H // side, stride=side), :]
    for i in range(1, side):
        rows = jnp.where(
            lane // d == i,
            pltpu.roll(scratch[pl.ds(i, H // side, stride=side), :], i * d, 1),
            rows)
    return rows


@functools.partial(jax.jit, static_argnames=("side", "interpret"))
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
    side: int = 1,              # narrow KV heads side by side in a page row
    interpret: bool = False,
) -> jnp.ndarray:
    return _call(_kernel, block_tables, seq_lens, seq_lens, (), q, k_pages,
                 v_pages, layer, cur_k, cur_v, block_tables.shape[1],
                 side=side, interpret=interpret)


def _window_kernel(bt_ref, run_ref, sl_ref, skip_ref, first_ref, layer_ref,
                   *refs, **kw):
    """:func:`_kernel` over the pages a lane's window reaches
    (ops/attention.window_cut): the walk starts at entry ``first_ref`` [B] of
    the lane's table row, ``sl_ref`` counts from that page, ``skip_ref`` [B]
    is how many rows from there lie before the window."""
    _kernel(bt_ref, run_ref, sl_ref, layer_ref, *refs, skip_ref=skip_ref,
            first_ref=first_ref, **kw)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def swa_paged_decode_attention_kernel(
    q: jnp.ndarray,            # [B, H, D]
    k_pages: jnp.ndarray,      # [Lw, N, block, Hkv, D] — the window layers' pools
    v_pages: jnp.ndarray,
    layer: jnp.ndarray,         # int32 scalar
    block_tables: jnp.ndarray,  # [B, maxB] int32, by logical page
    seq_lens: jnp.ndarray,      # [B] int32 (incl. current token)
    cur_k: jnp.ndarray,         # [B, Hkv, D]
    cur_v: jnp.ndarray,
    *,
    window: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """ops/attention.swa_paged_decode_attention, as a kernel: a query sees
    its own K/V and the ``window - 1`` rows cached before it."""
    block = k_pages.shape[2]
    first, lens, skip = window_cut(seq_lens, block, window, align=RUN_PAGES)
    # The table whole, as the full layers' walk takes its own: the kernel
    # reads it from ``first`` on, and its groups of entries are the table's
    # own aligned groups (``first`` is a multiple of RUN_PAGES).
    return _call(_window_kernel, block_tables, seq_lens, lens, (skip, first),
                 q, k_pages, v_pages, layer, cur_k, cur_v,
                 min(block_tables.shape[1],
                     window_pages(block, window, align=RUN_PAGES)),
                 interpret=interpret, name="swa_paged_decode_attention")


def _call(kernel, tables, seq_lens, lens, more, q, k_pages, v_pages, layer,
          cur_k, cur_v, walk: int, *, interpret: bool,
          name: str | None = None, side: int = 1):
    """One program a batch row over the stacked pools. The kernel's scalar
    operands: ``tables`` [B, maxB], which of their groups are runs (by
    ``seq_lens``, the lanes' whole lengths), ``lens`` [B] (the lengths the
    walk counts), whatever ``more`` holds, and the layer. ``walk``: the table
    entries a lane's walk reads at most, which bounds a stage. The query
    goes in in the pool's dtype; no operation stands between the caller's
    arrays and the kernel, or behind it. ``side`` > 1: a page row is that
    many narrow heads side by side, and the result is ``[B, H / side, D]``,
    as many query heads' outputs side by side a row (the module's
    docstring)."""
    B, H, D = q.shape
    _, _, block, n_kv, _ = k_pages.shape
    maxB = tables.shape[1]
    pages = pages_per_stage(block, n_kv, D, k_pages.dtype.itemsize, walk)
    group = run_pages(pages)
    prefetch = (tables.reshape(-1),
                table_runs(tables, seq_lens, block, group).reshape(-1), lens,
                *more)

    kernel = functools.partial(
        kernel, max_blocks=maxB, pages=pages, block=block, group=group,
        **({"side": side} if side > 1 else {}))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch) + 1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H // side, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, block, n_kv, D), k_pages.dtype),
            pltpu.VMEM((2, pages, block, n_kv, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            *([pltpu.VMEM((H, D), jnp.float32)] if side > 1 else []),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H // side, D), q.dtype),
        interpret=interpret,
        name=name,      # the op's in a device trace; None: the caller's own
    )(*prefetch, jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(k_pages.dtype), cur_k, cur_v, k_pages, v_pages)


# ---- a window of a prompt over the pages before it -----------------------------
#
# A continuation window of a long prompt (models/llama.py
# ``_mixed_prefill_with_prefix``): S queries at positions ``prefix_len ...``
# against the ``prefix_len`` rows the earlier windows left in the pools and
# against the window's own new K/V, which are not in the pages yet. The op is
# ``kv_window_prefill_attention`` in a device trace. It is the decode walks'
# stages (``stage_fetch``: the pages of the table as it stands, runs of
# adjacent pages as one copy, two slots) under a tile of queries instead of
# one query a lane:
#
# - A program a tile of ``QUERY_TILE`` queries, every KV head in it, so a
#   page is fetched once a tile for all its heads. A KV head's query heads are
#   folded into the rows ([heads a group x queries, D]: 7 x 256 at
#   SmallThinker's 28 on 4), so a KV head's rows meet the MXU once.
# - A stage's tile lies in VMEM as the page lies in the pool, row (token, KV
#   head). One head's rows are every ``Hkv``-th of them: a strided load (of
#   32-bit words, two heads of a 16-bit pool at once; ``_head_rows``, which
#   the decode walks take their heads' rows out with too), laid out a KV
#   head apart in a step's scratch.
# - The scores stay in VMEM, a running maximum and sum a query row in f32
#   scratch. Products in the pools' dtype with f32 accumulation, the
#   probabilities rounded to the pool's dtype for the second product: the
#   arithmetic ops/attention.banded_attention states, in another order of
#   the softmax's sums.
# - It walks what the prompt holds: ``cdiv(prefix_len - first_pos, block)``
#   pages from the table's first, whatever the table's width (the prior
#   bucket); a stage wholly before a tile's band (a window layer) is skipped,
#   as are the own rows in a tile's future. The own rows come first, from
#   VMEM, a stage's worth at a time; the first stage's copies start under
#   the last of them.
# - **Its text is set-up time.** Mosaic unrolls a [7 x 256, 512] step into
#   every vector register it touches, and the engine traces and lowers the
#   kernel for each of a cell's 19 continuation programs at every start,
#   cache or no cache, about a millisecond an operation of the traced body
#   on the chip's host. So the products and the softmax stand ONCE in it:
#   one loop over the steps (own chunks, then stages) around one loop over
#   the KV heads, one ``start`` of a stage's copies; and the layers of BOTH
#   kinds of a model take the same call, the kind a traced flag that picks
#   the pool pair a copy reads (``stage_fetch``), the table's half and the
#   band, so a program holds the kernel once and not once a ``cond`` branch,
#   and its shapes do not depend on the prior table's bucket. (Unrolled over
#   4 heads and 2 kinds of step, a call a branch, it cost the cell 31% of a
#   warm ``setup_s``; with the loops alone 12%: PERF.md section 6, PR 51.)

# Queries a program.
QUERY_TILE = 256
_PREFILL_VMEM_BYTES = 64 * 2 ** 20
# The band of a layer that sees everything.
_NO_BAND = 1 << 30


def _head_rows(tile_ref, slot, n_kv: int):
    """The ``[P, block, Hkv, D]`` tile of ``slot`` as a list of ``[P * block,
    D]`` arrays, one a KV head, in the tile's dtype: head g's rows are every
    ``Hkv``-th of the tile as it lies."""
    pages, block, _, head_dim = tile_ref.shape[1:]
    rows = pages * block
    flat = tile_ref.at[slot].reshape(rows * n_kv, head_dim)
    if tile_ref.dtype.itemsize == 4:
        return [flat[pl.ds(g, rows, stride=n_kv), :] for g in range(n_kv)]
    assert tile_ref.dtype == jnp.bfloat16 and n_kv % 2 == 0, (
        "a 16-bit pool's KV heads are taken out of a page in pairs",
        tile_ref.dtype, tile_ref.shape)
    # Rows (t, 2j) and (t, 2j + 1) share a 32-bit word, low half first: a
    # bf16 is the high half of the f32 of its value.
    words = flat.bitcast(jnp.uint32)
    heads = []
    for j in range(n_kv // 2):
        pair = words[pl.ds(j, rows, stride=n_kv // 2), :]
        heads += [
            pltpu.bitcast(pair << 16, jnp.float32).astype(jnp.bfloat16),
            pltpu.bitcast(pair & jnp.uint32(0xFFFF0000),
                          jnp.float32).astype(jnp.bfloat16)]
    return heads


def _prefill_kernel(bt_ref, run_ref, meta_ref,   # scalar prefetch
                    q_ref,               # [Hkv, G, Sq, D] — this tile's queries
                    own_k_ref, own_v_ref,    # [Hkv, S, D] — the window's own
                    k_hbm, v_hbm, near_k_hbm, near_v_hbm,   # pools (ANY/HBM)
                    out_ref,             # [Hkv, G, Sq, D]
                    k_tile, v_tile, sem_k, sem_v,   # [2, P, block, Hkv, D]
                    k_step, v_step,      # [Hkv, P * block, D] — a step's rows
                    m_sc, l_sc, acc_sc,  # [Hkv, G * Sq, 1 | 1 | D] f32
                    *, width: int, near_width: int, pages: int, block: int,
                    group: int, window: int):
    i = pl.program_id(0)
    n_kv, per_kv, sq, head_dim = q_ref.shape
    rows = pages * block                       # rows a step: a stage's
    scale = 1.0 / (head_dim ** 0.5)
    prefix_len, suffix_len, layer = meta_ref[0], meta_ref[1], meta_ref[2]
    near = meta_ref[3] > 0
    # The positions of the table's first row, the band, and where in the
    # prefetched tables the layer's own starts.
    first_pos = jnp.where(near, meta_ref[4], 0)
    band = jnp.where(near, window, _NO_BAND)
    first_entry = jnp.where(near, width, 0)

    def over(a, b: int):
        """a // b of a count (``//`` traces a dozen operations to floor a
        negative quotient)."""
        return jax.lax.div(a, jnp.int32(b))

    n_pages = over(prefix_len - first_pos + (block - 1), block)
    n_stages = over(n_pages + (pages - 1), pages)
    # The tile's queries, and the first row any of them sees.
    q_pos = prefix_len + i * sq + jax.lax.broadcasted_iota(
        jnp.int32, (sq, 1), 0)
    reach = jnp.maximum(prefix_len + i * sq - (band - 1), first_pos)
    # Steps: the own rows' chunks from the one ``reach`` lies in (or the
    # first) to the one the tile's last query lies in, then the stages from
    # ``reach``'s (past the last where no cached row is in reach).
    c_lo = over(jnp.maximum(reach - prefix_len, 0), rows)
    n_own = over((i + 1) * sq + (rows - 1), rows) - c_lo
    s_lo = jnp.minimum(over(reach - first_pos, rows), n_stages)

    _start, _wait = stage_fetch(
        bt_ref, run_ref, (near, k_hbm, near_k_hbm), k_tile, sem_k,
        zero_rest=False, also=(((near, v_hbm, near_v_hbm), v_tile, sem_v,
                                True),),
        lane=0, layer=layer, n_pages=n_pages, max_blocks=width + near_width,
        group=group, first=first_entry)

    m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
    l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)

    def step(t, carry):
        own = t < n_own
        s = s_lo + t - n_own

        # The next step's stage, if it is one: its copies fly under this
        # step's products (the first stage's under the last own chunk's).
        @pl.when((t + 1 >= n_own) & (s + 1 < n_stages))
        def _fetch_ahead():
            _start(s + 1, (s + 1) & 1)

        @pl.when(own)
        def _own_chunk():
            at = pl.multiple_of((c_lo + t) * rows, rows)
            k_step[...] = own_k_ref[:, pl.ds(at, rows), :]
            v_step[...] = own_v_ref[:, pl.ds(at, rows), :]

        @pl.when(jnp.logical_not(own))
        def _stage():
            _wait(s, s & 1)
            # (The cached rows in the new rows' dtype, a KV head apart.)
            for g, (k, v) in enumerate(zip(_head_rows(k_tile, s & 1, n_kv),
                                           _head_rows(v_tile, s & 1, n_kv))):
                k_step[g] = k.astype(k_step.dtype)
                v_step[g] = v.astype(v_step.dtype)

        # The step's rows' positions, and which of them exist: the cached
        # ones below the prefix, the own ones below the window's length.
        at = jnp.where(own, prefix_len + (c_lo + t) * rows,
                       first_pos + s * rows) + col
        seen = ((at <= q_pos) & (q_pos - at < band)
                & (at < prefix_len + jnp.where(own, suffix_len, 0)))

        def head(g, carry):
            """One step of KV head ``g``'s running softmax. (A query that
            has seen nothing yet weighs what it is shown by 1; its first
            seen row, and every query sees its own, scales that to
            nothing.)"""
            logits = jax.lax.dot_general(
                q_ref[g].reshape(per_kv * sq, head_dim), k_step[g],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [G * Sq, rows]
            logits = jnp.where(seen[None], logits.reshape(per_kv, sq, rows),
                               NEG_INF).reshape(per_kv * sq, rows)
            m = m_sc[g]
            new_m = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - new_m)
            corr = jnp.exp(m - new_m)
            l_sc[g] = l_sc[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_sc[g] = acc_sc[g] * corr + jnp.dot(
                p.astype(v_step.dtype), v_step[g],
                preferred_element_type=jnp.float32)
            m_sc[g] = new_m
            return carry

        return jax.lax.fori_loop(0, n_kv, head, carry)

    jax.lax.fori_loop(0, n_own + n_stages - s_lo, step, 0)
    out = acc_sc[...] / l_sc[...]
    out_ref[...] = out.reshape(out_ref.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def kv_window_prefill_attention(
    q: jnp.ndarray,             # [1, S, H, D] — the window's queries
    k_new: jnp.ndarray,         # [1, S, Hkv, D] — its own K/V, not in the pages
    v_new: jnp.ndarray,
    k_pages: jnp.ndarray,       # [L, N, block, Hkv, D] — the pools of the layers
    v_pages: jnp.ndarray,       # that see everything
    near_k_pages: jnp.ndarray,  # [Lw, Nw, block, Hkv, D] — and of those that
    near_v_pages: jnp.ndarray,  # see a window
    is_near: jnp.ndarray,       # bool scalar — this layer is one of the latter
    layer: jnp.ndarray,         # int32 scalar — which of its kind
    table_row: jnp.ndarray,     # [1, W] int32 — the sequence's pages, from 0
    near_table_row: jnp.ndarray,    # [1, Ww] — its window pool's, those that
    near_first_pos: jnp.ndarray,    # end where the window starts, from here [1]
    prefix_len: jnp.ndarray,    # [1] int32 — rows cached: the window's start
    suffix_len: jnp.ndarray,    # [1] int32 — the window's real tokens
    *,
    window: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """ops/attention.banded_attention of a continuation window over the
    cached rows and its own, for a layer of either kind of a model with
    both (``is_near``, traced: a scan decides it). The cached rows are read
    out of the layer's kind's stacked pools by that kind's table: positions
    0 on of ``table_row``, ``near_first_pos`` on of ``near_table_row``, in
    either those below ``prefix_len`` (a table's entries past them are never
    read). A query at t of a window layer sees s with ``0 <= t - s <
    window``, of the other kind every s up to t. Returns [1, S, H, D] in
    q.dtype; the rows past ``suffix_len`` are nobody's."""
    _, S, H, D = q.shape
    _, _, block, n_kv, _ = k_pages.shape
    assert near_k_pages.shape[2:] == k_pages.shape[2:], (
        near_k_pages.shape, k_pages.shape)
    per_kv = H // n_kv
    sq = min(QUERY_TILE, -(-S // 16) * 16)
    s_pad = -(-S // sq) * sq
    pages = pages_per_stage(block, n_kv, D, k_pages.dtype.itemsize,
                            min(table_row.shape[1], near_table_row.shape[1]))
    group = run_pages(pages)
    rows = pages * block
    own_pad = -(-s_pad // rows) * rows      # the own rows in whole steps
    # The two tables one after the other, each in whole groups.
    tables, runs = zip(*(
        (jnp.pad(t, ((0, 0), (0, -t.shape[1] % group))),
         table_runs(t, prefix_len - first + 1, block, group))
        for t, first in ((table_row, 0), (near_table_row, near_first_pos))))

    def by_head(x, to):     # [1, S, Hkv, ..., D] -> [Hkv, ..., to, D]
        x = jnp.moveaxis(x[0], 0, -2)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, to - S), (0, 0)])

    kernel = functools.partial(
        _prefill_kernel, width=tables[0].shape[1],
        near_width=tables[1].shape[1], pages=pages, block=block, group=group,
        window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_pad // sq,),
        in_specs=[
            pl.BlockSpec((n_kv, per_kv, sq, D), lambda i, *_: (0, 0, i, 0)),
            pl.BlockSpec((n_kv, own_pad, D), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((n_kv, own_pad, D), lambda i, *_: (0, 0, 0)),
            *[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        ],
        out_specs=pl.BlockSpec((n_kv, per_kv, sq, D),
                               lambda i, *_: (0, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, block, n_kv, D), k_pages.dtype),
            pltpu.VMEM((2, pages, block, n_kv, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((n_kv, rows, D), k_new.dtype),
            pltpu.VMEM((n_kv, rows, D), v_new.dtype),
            pltpu.VMEM((n_kv, per_kv * sq, 1), jnp.float32),
            pltpu.VMEM((n_kv, per_kv * sq, 1), jnp.float32),
            pltpu.VMEM((n_kv, per_kv * sq, D), jnp.float32),
        ],
    )
    one = functools.partial(jnp.reshape, shape=(1,))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_kv, per_kv, s_pad, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="kv_window_prefill_attention",
    )(jnp.concatenate(tables, axis=1).reshape(-1),
      jnp.concatenate(runs, axis=1).reshape(-1),
      jnp.concatenate([prefix_len, suffix_len, one(layer), one(is_near),
                       near_first_pos]).astype(jnp.int32),
      by_head(q.reshape(1, S, n_kv, per_kv, D), s_pad),
      by_head(k_new, own_pad), by_head(v_new, own_pad), k_pages, v_pages,
      near_k_pages, near_v_pages)
    return jnp.moveaxis(out[:, :, :S], 2, 0).reshape(1, S, H, D)
