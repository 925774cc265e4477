"""Grouped-matmul MoE FFN for Mixtral-family models, a form of few rows that
reads the chosen experts alone, and the rules that say when each serves.

The dense-over-experts form in models/llama.py:_moe_ffn computes every expert
for every token: E/k times the FLOPs a token needs. Below the chip's ridge
(a decode chunk's 2-16 rows) the FLOPs cost nothing, and both forms are the
read of every expert's weights -- WHERE every expert has a row: a chip that
holds all of its router's experts at a full batch (Mixtral: 16 rows x 2
choices over 8 experts, an expert without a row is a 1% event), and dense has
no sort, scatter or padding. Above the ridge (a prefill of hundreds of tokens)
the extra FLOPs are time. And a chip that holds a RANGE of the experts its
router scores sees about one row an expert a decode step: a third of the held
experts have no row, and dense reads their weights for nobody. So the form is
chosen per traced program, from its shapes: :func:`use_grouped`,
:func:`use_chosen`.

The grouped form computes only the (token, chosen expert) rows:

1. XLA side (:func:`grouped_experts`, :func:`group_layout`): each token
   becomes its k (token, expert) rows, laid out *group-padded*: every
   expert's rows start at a row-tile boundary, in the order a stable sort by
   expert would give. A row's place comes from COUNTING the earlier rows of
   its expert (a product with a triangle of ones), the inverse from one sort
   of the rows' keys read a tile's slice at a time; nothing is scattered.
   The buffer is static, T·k + E·tm rows; only the offsets are data, so no
   token is ever dropped, whatever the routing. A tile → expert map and the
   count of tiles that hold rows go to the kernel as prefetched scalars.
2. Pallas side (:func:`_grouped_matmul`), twice: ``silu(x·w1) * (x·w3)`` →
   ``[Tp, F]``, then ``·w2``. The grid is (N tiles, K tiles, row tiles) with
   the ROW tiles innermost: consecutive row tiles of one expert map to the
   same weight block, which the pipeline then does not fetch again, so an
   expert's weights are read once a layer and not once a row tile. Partial
   sums over K wait in an f32 scratch that holds every row tile. Tiles past
   the last group hold no rows: they are skipped, and their block indices
   repeat the last live tile's so that nothing is copied for them.
3. Back in XLA: gather each token's k rows out of the padded layout a choice
   at a time, weight them by the router's gates and add: one pass.

The form of few rows (:func:`chosen_experts`) is the same kernel with ONE
row tile, the program's rows whole, shared by every expert some row chose: no
sort, no gather, no group padding; the tile -> expert map is the list of
chosen experts, and the tiles past its end are skipped as above. What it
saves is the unchosen experts' weights.

bf16 operands and f32 accumulation, as the dense einsums have them. Reference
analogue: none — the reference router is control-plane Go (SURVEY.md); the
layout follows the public megablox / ragged-matmul pattern (PAPERS.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of one tile: the MXU's height. An expert's group is padded to it.
ROW_TILE = 128

# Token count of a program from which the grouped form serves: twice the
# ridge. A v5e's ridge is 197 TFLOP/s / 819 GB/s = 240 rows of bf16, whatever
# the expert count: under it both forms take the time of reading every
# expert's weights, and at it they still measure the same (one Mixtral layer
# at 256 tokens: dense 4.40 ms, grouped 4.27; at 512: 8.47 and 5.33; PERF.md
# section 6, PR 31), so the line stands where the gain starts to pay for two
# more kernel programs traced at every start.
GROUPED_MIN_TOKENS = 512

# Rows an expert can expect of a program (rows x experts_per_token / router
# outputs) up to which a program of one row tile reads the chosen experts'
# weights alone (:func:`use_chosen`). The kernel reads an expert's weights as
# fast as XLA's einsums do (700-735 GB/s of those it reads, at every width
# below), so it wins what the unchosen experts are, less a combine: one layer
# on the chip, dense / chosen ms (PERF.md section 6, PR 46) -- at 1.0 rows an
# expert 1.72 / 1.16 (LongCat, 64 rows, 16 held) and 1.98 / 1.30 (DeepSeek,
# 32 rows); at 2.0, 2.04 / 1.82 (dots3, 64 rows, 32 held); at 2.75, 1.91 /
# 1.80 (Nemotron, 64 rows, 128 held, not gated); at 4.0, 1.92 / 1.94 and
# 2.06 / 2.07: even; at 5.5, 1.90 / 2.19. The line stands between the last
# gain and the first draw.
CHOSEN_MAX_ROWS_PER_EXPERT = 3.0

# Rows the one shared tile of :func:`chosen_experts` is padded to: a bf16
# tile's sublanes.
_CHOSEN_ROW_ALIGN = 16

# Rows of one block of the layout's count (:func:`group_layout`): a product
# with a triangle of ones that is one pass of the MXU.
_COUNT_BLOCK = 128

# What one grouped matmul may keep in VMEM (of a v5e's 128 MiB; the
# compiler's default scoped limit of 16 MiB is raised to what the tiles need).
VMEM_BUDGET_BYTES = 40 * 2 ** 20


def use_grouped(tokens: int, *, n_experts: int, experts_per_token: int,
                d_model: int, d_ff: int, platform: str, sharded: bool,
                interpret: bool = False) -> bool:
    """Whether a program that puts ``tokens`` rows (batch x sequence, padded)
    through the MoE FFN computes the chosen experts' rows alone (True) or
    every expert for every row (False). One rule, from what is known when
    the program is traced: the grouped form serves where it compiles and
    wins — a real TPU (or the interpreter, for tests), whole weights on one
    device (a sharded engine keeps the dense einsums, which XLA partitions),
    widths the kernel can tile, fewer chosen experts than experts, and a token
    count past the ridge."""
    if sharded or not (platform == "tpu" or interpret):
        return False
    if not 0 < experts_per_token < n_experts:
        return False
    if d_model % 128 or d_ff % 128:
        return False
    return tokens >= GROUPED_MIN_TOKENS


def use_chosen(tokens: int, *, router_outputs: int, experts_per_token: int,
               n_experts: int, held: int, d_model: int, d_ff: int,
               platform: str, sharded: bool, interpret: bool = False) -> bool:
    """Whether a program of ``tokens`` rows runs its held experts dense over
    those a row chose (:func:`chosen_experts`) and not over all of them. From
    what is known when the program is traced: where the kernel compiles (as
    :func:`use_grouped`), on a chip that holds a RANGE of the ``n_experts``
    its router scores (``held`` of them; one that holds them all sees every
    row's every choice, three rows an expert and more at a full batch), for
    rows that are one tile, and while the rows an expert can expect -- rows x
    choices over the router's outputs, its zero-compute ones among them --
    leave enough experts without a row to pay for the kernel."""
    if sharded or not (platform == "tpu" or interpret):
        return False
    if not 0 < held < n_experts or d_model % 128 or d_ff % 128:
        return False
    return (tokens <= ROW_TILE and tokens * experts_per_token
            <= CHOSEN_MAX_ROWS_PER_EXPERT * router_outputs)


def _vmem_bytes(n_row_tiles: int, tm: int, tk: int, tn: int, n_rhs: int,
                itemsize: int, k_tiles: int) -> int:
    """VMEM one grouped matmul holds: weight blocks, lhs and out row tiles,
    each double-buffered by the pipeline, and, where K is tiled, the f32
    partial sums of every row tile."""
    rhs = n_rhs * 2 * tk * tn * itemsize
    rows = 2 * tm * tk * itemsize + 2 * tm * tn * itemsize
    acc = n_rhs * n_row_tiles * tm * tn * 4 if k_tiles > 1 else 0
    return rhs + rows + acc


# A grid step's fixed cost (about 0.35 us) in the bytes the chip reads
# meanwhile: what pick_tiles weighs many small steps against.
_STEP_BYTES = 256 * 2 ** 10


def pick_tiles(rows: int, k_dim: int, n_dim: int, n_rhs: int, itemsize: int,
               tm: int = ROW_TILE) -> tuple[int, int]:
    """(tk, tn) of one grouped matmul [rows, K] x [E, K, N]: lane-aligned
    divisors of K and N that fit the VMEM budget. The weights are read once
    whatever the tiles; the lhs is read again for every N tile, and every
    grid step has a fixed cost: the cheapest sum of the two wins."""
    def divisors(n):
        return [t for t in range(128, n + 1, 128) if n % t == 0]

    def cost(tk, tn):
        n_tiles = n_dim // tn
        steps = n_tiles * (k_dim // tk) * (rows // tm)
        return n_tiles * rows * k_dim * itemsize + steps * _STEP_BYTES

    fits = [(cost(tk, tn), tk, tn)
            for tn in divisors(n_dim) for tk in divisors(k_dim)
            if _vmem_bytes(rows // tm, tm, tk, tn, n_rhs, itemsize,
                           k_dim // tk) <= VMEM_BUDGET_BYTES]
    if not fits:
        raise ValueError(
            f"grouped MoE: no tile of [{rows}, {k_dim}] x [{k_dim}, {n_dim}] "
            f"fits {VMEM_BUDGET_BYTES >> 20} MiB of VMEM")
    return min(fits)[1:]


def _gmm_kernel(tile_expert, n_live, layer, lhs_ref, *refs, n_rhs: int,
                k_tiles: int, relu2: bool = False, reglu: bool = False):
    """One (N tile, K tile, row tile) grid step: the row tile's [tm, tk]
    against its expert's [tk, tn] block(s). With two weight operands the
    result is silu(lhs·rhs0) * (lhs·rhs1), or with ``reglu`` relu(lhs·rhs0)
    * (lhs·rhs1); with one and ``relu2`` it is relu(lhs·rhs0) squared (an
    expert that is not gated)."""
    del tile_expert, layer  # read by the index maps
    rhs_refs, out_ref, acc_refs = refs[:n_rhs], refs[n_rhs], refs[n_rhs + 1:]
    k, i = pl.program_id(1), pl.program_id(2)

    def finish(parts):
        if reglu:
            y = jnp.maximum(parts[0], 0.0) * parts[1]
        else:
            y = parts[0] if n_rhs == 1 else jax.nn.silu(parts[0]) * parts[1]
        if relu2:
            y = jnp.square(jnp.maximum(y, 0.0))
        out_ref[...] = y.astype(out_ref.dtype)

    @pl.when(i < n_live[0])
    def _live():
        lhs = lhs_ref[...]
        parts = [jax.lax.dot_general(lhs, r[...], (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 for r in rhs_refs]
        if k_tiles == 1:
            finish(parts)
            return

        @pl.when(k == 0)
        def _first():
            for acc, part in zip(acc_refs, parts):
                acc[i] = part

        @pl.when(k != 0)
        def _add():
            for acc, part in zip(acc_refs, parts):
                acc[i] += part

        @pl.when(k == k_tiles - 1)
        def _flush():
            finish([acc[i] for acc in acc_refs])


def _grouped_matmul(lhs, rhs, layer, tile_expert, n_live, *,
                    tm: int = ROW_TILE, tiles: tuple[int, int] | None = None,
                    interpret: bool = False, relu2: bool = False,
                    reglu: bool = False, shared_tiles: int = 0):
    """lhs [Tp, K] (group-padded rows) times the expert of each row tile out
    of every ``rhs`` [L, E, K, N] at ``layer``; one rhs → lhs·rhs (``relu2``:
    its relu squared), two → the SwiGLU of both (``reglu``: their ReGLU).
    The weights come stacked over layers, with the layer a prefetched scalar [1]: one layer's slice of them would reach the kernel
    as a copy (a custom call's operand cannot be a fused slice), 2.8 GB a
    Mixtral layer. tile_expert [Tp // tm] int32; n_live [1] int32, the tiles
    that hold rows. Returns [Tp, N] in lhs.dtype; rows of tiles past n_live
    are not written. ``shared_tiles`` n: lhs is ONE tile [tm, K] that each of
    n tiles multiplies by its own expert (:func:`chosen_experts`); it stays
    in VMEM from tile to tile, and the result is [n * tm, N]."""
    K = lhs.shape[1]
    N = rhs[0].shape[3]
    n_rhs, n_row_tiles = len(rhs), shared_tiles or lhs.shape[0] // tm
    Tp = n_row_tiles * tm
    itemsize = jnp.dtype(lhs.dtype).itemsize
    tk, tn = tiles or pick_tiles(Tp, K, N, n_rhs, itemsize, tm)
    k_tiles = K // tk

    def row(i, live):  # a tile past the last group repeats the last live one
        return jnp.minimum(i, live[0] - 1)

    def out_map(n, k, i, te, live, layer):
        # Written on the last K tile; until then the index stands still, so
        # the pipeline copies no block out that holds nothing yet.
        return jnp.where(k == k_tiles - 1, row(i, live), 0), n

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N // tn, k_tiles, n_row_tiles),
        in_specs=[pl.BlockSpec(
            (tm, tk), lambda n, k, i, te, live, layer:
            (0 if shared_tiles else row(i, live), k))]
        + [pl.BlockSpec(
            (None, None, tk, tn), lambda n, k, i, te, live, layer:
            (layer[0], te[row(i, live)], k, n))] * n_rhs,
        out_specs=pl.BlockSpec((tm, tn), out_map),
        scratch_shapes=[pltpu.VMEM((n_row_tiles, tm, tn), jnp.float32)
                        ] * (n_rhs if k_tiles > 1 else 0),
    )
    need = _vmem_bytes(n_row_tiles, tm, tk, tn, n_rhs, itemsize, k_tiles)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_rhs=n_rhs, k_tiles=k_tiles,
                          relu2=relu2, reglu=reglu),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=need + 8 * 2 ** 20),
        interpret=interpret,
        # The op's name in a device trace, for whoever reduces one.
        name=("moe_grouped_reglu" if reglu else
              "moe_grouped_swiglu" if n_rhs == 2 else
              "moe_grouped_relu2" if relu2 else "moe_grouped_matmul"),
    )(tile_expert, n_live, layer, lhs, *rhs)


def _stacked(lp, layer, gated: bool):
    """(The up weights, (w2,), the layer [1] int32) as :func:`_grouped_matmul`
    takes them: every layer's weights and ``layer``, or one layer's (``layer``
    None) as a stack of one."""
    up = ("w1", "w3") if gated else ("w1",)
    if layer is None:
        *w_up, w2 = (lp[n][None] for n in (*up, "w2"))
        layer = jnp.zeros((), jnp.int32)
    else:
        *w_up, w2 = (lp[n] for n in (*up, "w2"))
    return tuple(w_up), (w2,), layer.reshape(1).astype(jnp.int32)


def moe_ffn_grouped(lp, x, n_experts: int, experts_per_token: int,
                    *, layer=None, tm: int = ROW_TILE, interpret: bool = False,
                    tiles_up: tuple[int, int] | None = None,
                    tiles_down: tuple[int, int] | None = None) -> jnp.ndarray:
    """Drop-in for models.llama._moe_ffn (same mathematics, grouped).

    lp: layer params with router/w1/w3/w2 ([E,D,F]/[E,F,D] stacked experts).
    With ``layer`` (an int32 scalar) w1/w3/w2 are every layer's, [L,E,D,F] /
    [L,E,F,D], and the kernel reads that layer's in place: how a scan over
    layers hands them over (models.llama._over_layers).
    x: [B, S, D]. Returns [B, S, D] in x.dtype. ``tm`` and the (tk, tn) of
    the two matmuls are the tests' and the microbench's to set; served, they
    come from the shapes (:func:`pick_tiles`).
    """
    from ..models import scopes     # (at call time: models imports ops)

    B, S, D = x.shape
    xt = x.reshape(B * S, D)

    # The router's logits in f32 straight from the product, said outright: on
    # a TPU that is what _moe_ffn's cast of a bf16 product compiles to as
    # well (the compiler keeps the accumulator's excess precision), and a
    # logit rounded here and not there sends a token whose second and third
    # choice lie within bf16 rounding to another expert than the dense form.
    with scopes.block("ffn.router"):
        logits = jnp.dot(xt, lp["router"],
                         preferred_element_type=jnp.float32)    # [T, E]
        top_vals, top_idx = jax.lax.top_k(logits, experts_per_token)  # [T, k]
        gates = jax.nn.softmax(top_vals, axis=-1)               # [T, k]
    y = grouped_experts(lp, xt, top_idx, gates, n_experts, layer=layer, tm=tm,
                        interpret=interpret, tiles_up=tiles_up,
                        tiles_down=tiles_down)
    return y.reshape(B, S, D)


def grouped_experts(lp, xt, top_idx, gates, n_experts: int, *, layer=None,
                    tm: int = ROW_TILE, interpret: bool = False,
                    tiles_up: tuple[int, int] | None = None,
                    tiles_down: tuple[int, int] | None = None,
                    first: int | None = None,
                    gated: bool = True, reglu: bool = False) -> jnp.ndarray:
    """The routed experts' part of an MoE FFN, whoever routed: token t of
    ``xt`` [T, D] through its experts ``top_idx`` [T, k], weighted by
    ``gates`` [T, k] and added. Returns [T, D] in xt.dtype. The router (its
    scores, its selection, its gates) is the model's; the weights w1/w3/w2
    and ``layer`` are as :func:`moe_ffn_grouped` takes them.

    ``first`` says that the weights hold a range of the experts the router
    chose among: the ``n_experts`` from expert id ``first`` on. A (token,
    expert) row whose expert is absent is dropped before the group layout --
    its place is behind the last group, in rows no live tile covers, and it
    contributes nothing -- so the buffer stays T·k + n_experts·tm rows and no
    token is dropped at any skew. ``gated`` False: an expert is
    relu(x·w1)²·w2, and there is no w3. ``reglu``: a gated expert's
    activation is relu(x·w1) * (x·w3), not SwiGLU's."""
    T, D = xt.shape
    E = n_experts

    # The glue names itself in a device trace, inside whatever scope the
    # model gave the experts (models/scopes.py; imported at call time:
    # models imports ops).
    from ..models import scopes

    with scopes.block("ffn.experts.glue"):
        # The rows a choice at a time, [k, T]: the way back sums over the
        # major axis, and no index is transposed on the way.
        expert = top_idx.T                                          # [k, T]
        here = None
        if first is not None:
            here = (expert >= first) & (expert < first + E)
            expert = jnp.where(here, expert - first, E)     # absent: last
        dest, src, tile_expert, n_live = group_layout(
            expert, E, tm=tm, absent=first is not None)
        x_pad = _rows(xt, src)                                      # [Tp, D]

    w_up, w2, layer = _stacked(lp, layer, gated)
    h = _grouped_matmul(x_pad, w_up, layer, tile_expert, n_live,
                        tm=tm, tiles=tiles_up, interpret=interpret,
                        relu2=not gated, reglu=reglu)
    out_pad = _grouped_matmul(h, w2, layer, tile_expert, n_live,
                              tm=tm, tiles=tiles_down, interpret=interpret)

    with scopes.block("ffn.experts.glue"):
        # Token t's k result rows, times their gates in the rows' dtype,
        # summed in choice order: one gather and one pass over [k, T, D].
        rows = _rows(out_pad, dest).reshape(-1, T, D)
        if here is not None:
            # An absent expert's row lies where no tile wrote: whatever is there.
            rows = jnp.where(here[..., None], rows, 0)
        y = (rows * gates.T[..., None].astype(xt.dtype)).sum(axis=0)
        return y.astype(xt.dtype)


def _rows(table, index):
    """``table[index]`` along the first axis, for an index the caller made
    and knows to lie inside: no wrap of a negative one, no clamp."""
    return table.at[index.astype(jnp.uint32)].get(mode="promise_in_bounds")


def group_layout(expert, n_experts: int, *, tm: int = ROW_TILE,
                 absent: bool = False):
    """The group-padded layout of the (choice, token) rows ``expert`` [k, T]
    (a row's expert, 0 .. E - 1; E: an expert that is not held, where
    ``absent``), from COUNTING: a row's place is its group's start plus how
    many earlier rows chose the same expert -- what a stable sort by expert
    gives -- and a count is a product with a triangle of ones: no scatter
    makes an index, and small tables are summed under a mask, not gathered.
    Rows are taken in the order they lie in, a choice at a time. Expert e's
    rows start at off[e], every group padded up to a multiple of ``tm``; the
    buffer is static, Tp = k*T + E*tm rows made whole tiles; absent rows lie
    behind the last group in their own order, where no live tile is.

    Returns (dest [k*T]: a row's place in the buffer; src [Tp]: the token
    whose row a place holds, some token where it holds none; tile_expert
    [Tp // tm]: the expert whose group holds a tile's first row; n_live [1]:
    the tiles that hold rows, at least one where ``absent``), all int32."""
    k, T = expert.shape
    E, N = n_experts, k * T
    G = E + absent                  # the groups counted: absent rows, last
    i32 = jnp.int32

    # One key a row: its expert, then its choice, then its token, so that the
    # keys' order is the rows' stable order by expert and a key's low bits
    # are the row's token.
    t_bits, j_bits = (max(n - 1, 1).bit_length() for n in (T, k))
    assert G << (j_bits + t_bits) < 2 ** 31
    key = (((expert.astype(i32) << j_bits)
            + jax.lax.broadcasted_iota(i32, (k, T), 0)) << t_bits
           ) + jax.lax.broadcasted_iota(i32, (k, T), 1)
    key = key.reshape(-1)                                       # [N]

    # How many rows up to and with row i chose expert g, in two levels: inside
    # a block of _COUNT_BLOCK rows a product of a triangle of ones with the
    # rows' one-hot (0 / 1 in bf16, sums in f32: exact), across blocks a
    # running sum of the blocks' totals.
    nb = -(-N // _COUNT_BLOCK)
    blocks = jnp.pad(key >> (j_bits + t_bits), (0, nb * _COUNT_BLOCK - N),
                     constant_values=G).reshape(nb, _COUNT_BLOCK)
    hot = blocks[..., None] == jnp.arange(G, dtype=i32)         # [nb, B, G]
    tri = jnp.tril(jnp.ones((_COUNT_BLOCK,) * 2, jnp.bfloat16))
    within = jnp.einsum("ij,bjg->big", tri, hot.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32).astype(i32)
    upto = jnp.cumsum(within[:, -1], axis=0)                    # [nb, G]
    counts = upto[-1, :E]                                       # [E]

    # off[e], start[e]: the padded and the plain rows of the groups before e.
    padded = jax.lax.div(counts + (tm - 1), tm) * tm
    before = jnp.arange(E, dtype=i32) < jnp.arange(E + 1, dtype=i32)[:, None]
    off, start = (jnp.sum(jnp.where(before, n, 0), axis=-1)
                  for n in (padded, counts))                    # [E+1] each
    # A row's place: its group's start, the rows of it in earlier blocks,
    # the rows of it up to here in this block, less itself.
    base = off[:G] - 1 + upto - within[:, -1]                   # [nb, G]
    dest = jnp.sum(jnp.where(hot, within + base[:, None], 0),
                   axis=-1).reshape(-1)[:N]

    # A tile's expert is the one whose [off[e], off[e+1]) holds its first
    # row: as many groups end at or before it. The rows that stand before
    # that group in the sorted order, less those before it in the buffer,
    # turn a place into a rank; the rows up to and with the group bound it.
    tile_start = jnp.arange(-(-N // tm) + E, dtype=i32)[:, None] * tm
    ended, begun = off[1:] <= tile_start, off[:E] <= tile_start  # [tiles, E]
    tile_expert = jnp.minimum(jnp.sum(ended, axis=-1, dtype=i32), E - 1)
    shift = jnp.sum(jnp.where(ended, counts - padded, 0), axis=-1)
    bound = jnp.sum(jnp.where(begun, counts, 0), axis=-1)
    n_live = jnp.sum(tile_start < off[E:], axis=0, dtype=i32)   # [1]
    if absent:
        # A program none of whose choices is held has no group at all, and
        # the kernel's block index min(i, n_live - 1) would be -1: the chip
        # halts on that copy's bounds check (the interpreter clamps it and
        # says nothing). One tile then counts as live; what it computes lies
        # in places no choice has for its own.
        n_live = jnp.maximum(n_live, 1)

    # The way in is a gather too, so it needs the inverse: which row stands
    # r-th in expert e's group. One sort of the keys says it, and a tile's
    # places are consecutive ranks, tm of them from rank on: they lie in two
    # consecutive rows of the sorted keys laid tm a row, which are gathered
    # as ROWS and shifted under a mask. (A gather of single integers is 7 ns
    # an element on the chip, 58 us a layer at 8,192 places, and a slice at
    # a rank of its own a loop of 0.8 us a tile; PERF.md section 6, PR 55.)
    rows = N // tm + 2
    order = jnp.pad(jnp.sort(key, stable=False), (0, rows * tm - N))
    rank = jnp.minimum(tile_start + shift[:, None], N)          # [tiles, 1]
    row, lane = jax.lax.div(rank, tm), jax.lax.rem(rank, tm)
    pair = _rows(order.reshape(rows, tm),
                 jnp.concatenate([row, row + 1], axis=1))       # [tiles, 2, tm]
    at = jnp.arange(tm, dtype=i32)
    picks = (lane + at)[..., None] == jnp.arange(2 * tm, dtype=i32)
    token = jnp.sum(jnp.where(picks, pair.reshape(-1, 1, 2 * tm), 0),
                    axis=-1) & ((1 << t_bits) - 1)              # [tiles, tm]
    src = jnp.where(rank + at < bound[:, None], token, 0)
    return dest, src.reshape(-1), tile_expert, n_live


def chosen_experts(lp, xt, local, gates, n_experts: int, *, layer=None,
                   interpret: bool = False, gated: bool = True
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The routed experts' part of an MoE FFN for a program of few rows (one
    row tile or less): dense over the held experts that some row chose. Every
    such expert takes ALL of ``xt`` [T, D] -- under the ridge the rows cost
    nothing, so there is no sort, no gather and no group layout -- and an
    expert nobody chose is neither computed nor read: it contributes the zero
    it contributes to the dense form. ``local`` [T, k] is the held expert each
    choice names, 0 .. n_experts - 1, or -1: an expert not held here, or a row
    that is nobody's (a padding lane's choices make no expert live).
    ``gates`` [T, k]; the weights, ``layer`` and ``gated`` as
    :func:`grouped_experts` takes them. Returns ([T, D] in xt.dtype, the
    experts whose weights were read: int32 scalar)."""
    T, D = xt.shape
    E = n_experts
    tile = jnp.arange(E, dtype=jnp.int32)
    hit = local[..., None] == tile                                # [T, k, E]
    weights = jnp.einsum("tke,tk->te", hit.astype(xt.dtype),
                         gates.astype(xt.dtype))                  # [T, E]
    live = jnp.any(hit, axis=(0, 1))                              # [E]
    # Tile i is the i-th live expert; the tiles past them are skipped.
    tile_expert = jnp.nonzero(live, size=E, fill_value=0)[0].astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32)
    # A step in which nobody chose a held expert (two lanes of a ramp; every
    # warm-up program) has no live tile, and the kernel's block index
    # min(i, n_live - 1) would be -1: the chip halts on that copy's bounds
    # check (grouped_experts has the note). One tile then counts as live,
    # and is masked with the dead ones below.
    n_read = jnp.maximum(n_live, 1)

    Tp = -(-T // _CHOSEN_ROW_ALIGN) * _CHOSEN_ROW_ALIGN
    x_pad = jnp.pad(xt, ((0, Tp - T), (0, 0)))
    w_up, w2, layer = _stacked(lp, layer, gated)
    h = _grouped_matmul(x_pad, w_up, layer, tile_expert, n_read[None], tm=Tp,
                        interpret=interpret, relu2=not gated, shared_tiles=E)
    out = _grouped_matmul(h, w2, layer, tile_expert, n_read[None], tm=Tp,
                          interpret=interpret)

    # A dead tile's rows hold whatever was there: masked, not weighed by 0.
    is_live = tile < n_live
    out = jnp.where(is_live[:, None, None], out.reshape(E, Tp, D)[:, :T], 0)
    y = jnp.einsum("itd,ti->td", out,
                   jnp.where(is_live, weights[:, tile_expert], 0))
    return y.astype(xt.dtype), n_read
