"""Pallas grouped-matmul MoE FFN (megablox-style) for Mixtral-family models.

The dense-over-experts formulation in models/llama.py:_moe_ffn computes every
expert for every token — regular and shardable, but E/k× the necessary FLOPs
and it always streams ALL expert weights from HBM. This kernel computes only
the (token, selected-expert) pairs:

1. XLA side (:func:`moe_ffn_grouped`): router top-k → expand each token into
   its k (token, expert) rows → stable-sort rows by expert → scatter into a
   *group-padded* layout where each expert's rows start at a row-tile
   boundary (buffer size is static: T·k + E·TM rows; only the offsets are
   data). A tile→expert map is computed with a searchsorted.
2. Pallas side (:func:`_grouped_ffn_call`): grid (row_tiles, F_tiles); the
   tile→expert map is scalar-prefetched so each grid step's BlockSpec
   index_map pulls w1/w3/w2 slices of exactly the ONE expert this row tile
   belongs to (unused experts are never read from HBM). Each step computes
   silu(x@w1_f)·(x@w3_f) @ w2_f and accumulates the [TM, D] partial into the
   output tile across F steps (f32 accumulation, revisit pattern).
3. Back in XLA: gather rows out of the padded layout, weight by the router
   gates, and sum each token's k rows.

Reference analogue: none — the reference router is control-plane Go
(SURVEY.md preamble); this is the engine half's hot op. Design follows the
public megablox/ragged-matmul pattern (PAPERS.md) re-derived for this layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Row tiles the kernel serves with (see moe_ffn_grouped): the bf16 sublane
# floor for decode-scale token counts, MXU height for prefill-scale ones.
ROW_TILES = (16, 128)

# What one kernel may keep in VMEM: the TPU compiler's scoped limit. It
# charges a grid step exactly the terms _vmem_bytes counts (compiled for a
# described v5e: 15.47 MiB by this count passes, 16.24 MiB is refused with
# "Scoped allocation with size 16.24M"), so the limit itself is the budget.
VMEM_BUDGET_BYTES = 16 * 2 ** 20


def _vmem_bytes(d_model: int, tf: int, tm: int, itemsize: int) -> int:
    """VMEM one (row_tile, f_tile) grid step holds: the three weight blocks
    ([D, tf], [D, tf], [tf, D]) and the x / out row tiles, each
    double-buffered by the pipeline, plus the f32 accumulator."""
    weights = 3 * 2 * d_model * tf * itemsize
    rows = 2 * 2 * tm * d_model * itemsize
    acc = tm * d_model * 4
    return weights + rows + acc


def pick_ff_tile(d_model: int, d_ff: int, tm: int, itemsize: int,
                 tf: int = 512) -> int | None:
    """Largest lane-aligned (multiple-of-128) tile ≤ tf that divides d_ff
    and whose working set at row tile ``tm`` fits the VMEM budget; None when
    there is none (the grouped kernel then cannot serve this geometry). The
    one rule for the kernel's own tiling and for callers that gate on it."""
    candidates = [t for t in range(128, min(tf, d_ff) + 1, 128)
                  if d_ff % t == 0
                  and _vmem_bytes(d_model, t, tm, itemsize)
                  <= VMEM_BUDGET_BYTES]
    return candidates[-1] if candidates else None


def _ffn_kernel(tile_expert, x_ref, w1_ref, w3_ref, w2_ref, out_ref, acc_ref):
    """One (row_tile, f_tile) grid step: fused SwiGLU partial for one expert.

    out_ref maps only the row-tile grid axis, so it is revisited across the
    inner F axis; acc_ref scratch carries the f32 accumulation.
    """
    f = pl.program_id(1)
    x = x_ref[...]
    up = jax.lax.dot_general(x, w1_ref[0], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    gate = jax.lax.dot_general(x, w3_ref[0], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    act = (jax.nn.silu(up) * gate).astype(x.dtype)
    part = jax.lax.dot_general(act, w2_ref[0], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(f != 0)
    def _acc():
        acc_ref[...] += part

    @pl.when(f == pl.num_programs(1) - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def _grouped_ffn_call(x_pad, tile_expert, w1, w3, w2, *, tm: int, tf: int,
                      interpret: bool = False):
    """x_pad: [Tp, D] group-padded rows; tile_expert: [Tp//tm] int32;
    w1/w3: [E, D, F]; w2: [E, F, D]. Returns [Tp, D] in x_pad.dtype."""
    Tp, D = x_pad.shape
    F = w1.shape[2]
    n_row_tiles = Tp // tm
    n_f_tiles = F // tf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_row_tiles, n_f_tiles),
        in_specs=[
            pl.BlockSpec((tm, D), lambda i, f, te: (i, 0)),
            pl.BlockSpec((1, D, tf), lambda i, f, te: (te[i], 0, f)),
            pl.BlockSpec((1, D, tf), lambda i, f, te: (te[i], 0, f)),
            pl.BlockSpec((1, tf, D), lambda i, f, te: (te[i], f, 0)),
        ],
        out_specs=pl.BlockSpec((tm, D), lambda i, f, te: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)],
    )
    return pl.pallas_call(
        _ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, D), x_pad.dtype),
        interpret=interpret,
    )(tile_expert, x_pad, w1, w3, w2)


def moe_ffn_grouped(lp, x, n_experts: int, experts_per_token: int,
                    *, tm: int | None = None, tf: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """Drop-in for models.llama._moe_ffn's compute (same math, grouped).

    lp: layer params with router/w1/w3/w2 ([E,D,F]/[E,F,D] stacked experts).
    x: [B, S, D]. Returns [B, S, D] in x.dtype.

    Against the dense-over-experts einsums this should win where routing is
    sparse relative to the expert count and lose where every expert is hit
    anyway; the round-4 figures that said so (d=1024, f=4096) came from a
    stack that is gone and are to be re-measured. Dense stays the engine
    default; enable via pallas_moe for fine-grained-expert models. tm=None
    picks the row tile by shape: ROW_TILES[1] (MXU height) for prefill-scale
    token counts, ROW_TILES[0] (bf16 sublane floor) for decode.
    """
    B, S, D = x.shape
    E, k = n_experts, experts_per_token
    T = B * S
    if tm is None:
        tm = ROW_TILES[1] if T * k >= 1024 else ROW_TILES[0]
    F = lp["w1"].shape[2]
    # tf must divide F (the grid truncates otherwise — tail columns would be
    # silently dropped), be lane-aligned, and leave the grid step inside
    # VMEM. Pick the largest conforming tile no bigger than the requested one.
    chosen = pick_ff_tile(D, F, tm, jnp.dtype(x.dtype).itemsize, tf)
    if chosen is None:
        raise ValueError(
            f"d_ff={F} has no 128-aligned tile divisor ≤ {tf} that fits "
            f"VMEM at d_model={D}, row tile {tm}; use the dense MoE path "
            "for this geometry")
    tf = chosen
    xt = x.reshape(T, D)

    logits = (xt @ lp["router"]).astype(jnp.float32)            # [T, E]
    top_vals, top_idx = jax.lax.top_k(logits, k)                # [T, k]
    gates = jax.nn.softmax(top_vals, axis=-1)                   # [T, k]

    # Expand to T·k (token, expert) rows, stable-sorted by expert.
    flat_expert = top_idx.reshape(-1)                           # [T*k]
    order = jnp.argsort(flat_expert, stable=True)               # [T*k]
    src_token = order // k                                      # token of each sorted row
    sorted_expert = flat_expert[order]

    # Group-padded destination layout: expert e's rows start at off[e], each
    # group padded up to a multiple of tm. Static buffer: Tp = T*k + E*tm.
    counts = jnp.bincount(flat_expert, length=E)                # [E]
    padded = ((counts + tm - 1) // tm) * tm
    off = jnp.concatenate([jnp.zeros((1,), padded.dtype),
                           jnp.cumsum(padded)])                 # [E+1]
    # rank within group = position in sorted order minus group start in the
    # *unpadded* sorted layout.
    unpadded_start = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])    # [E+1]
    rank = jnp.arange(T * k) - unpadded_start[sorted_expert]
    dest = off[sorted_expert] + rank                            # [T*k]

    Tp = T * k + E * tm
    x_pad = jnp.zeros((Tp, D), x.dtype).at[dest].set(xt[src_token])

    # tile→expert: the expert whose [off[e], off[e+1]) range holds the tile's
    # first row (pure-padding tiles map to the previous/any expert — their
    # rows are zero and are never gathered back).
    tile_starts = jnp.arange(Tp // tm, dtype=jnp.int32) * tm
    tile_expert = (jnp.searchsorted(off[1:], tile_starts, side="right")
                   .astype(jnp.int32))
    tile_expert = jnp.minimum(tile_expert, E - 1)

    out_pad = _grouped_ffn_call(x_pad, tile_expert, lp["w1"], lp["w3"],
                                lp["w2"], tm=tm, tf=tf, interpret=interpret)

    rows = out_pad[dest]                                        # [T*k, D] sorted order
    # Un-sort back to (token, k) and gate-combine.
    unsorted = jnp.zeros_like(rows).at[order].set(rows)         # [T*k, D]
    y = (unsorted.reshape(T, k, D)
         * gates[..., None].astype(x.dtype)).sum(axis=1)
    return y.reshape(B, S, D).astype(x.dtype)
