"""The one-step recurrence of a state-space layer on the state pool, and the
rule that says which way its rows are fetched; and, below the Mamba-2 forms,
a Mamba-1 layer's two (a step in place in the pool's second layout, and a
prompt window's scan, which has no matrix form).

A decode step of a Mamba-2 layer (models/hybrid.py) changes every sequence's
state ``S [heads, head_dim, state]`` by ``S <- S * keep + (dt x) (x) B`` and
reads ``y = S C`` off the new state: six flops a value on 4 MB a sequence a
layer, so the time is the state's way through HBM and nothing else. One
mathematics, two ways to fetch its rows:

- :func:`update_rows`, the plain form: the rows gathered by slot as one array
  ``[B, heads, head_dim, state]``, the update and the read as XLA fuses them,
  and whoever called scatters the rows back. On a TPU that is eight passes
  over the rows (the gather's loop of slices into a zero-filled buffer, two
  fusions that each read them, the scatter; PERF.md section 6, PR 35). It is
  the CPU's path, a sharded pool's, and what the kernel is tested against.
- :func:`update_in_place`, the Pallas kernel: the pool goes in whole and comes
  out aliased to itself; ``slots`` and the layer are prefetched scalars and
  the block's index map reads ``slots[lane]``, so the pipeline copies a
  slot's ``[head block, head_dim, state]`` rows of that layer into VMEM, the
  kernel computes on them while they are there and the pipeline writes them
  back to the rows they came from: once in, once out. The pool comes stacked
  over layers for the reason ops/pallas_moe.py gives: a slice of it would
  reach a custom call as a copy.

float32 in the pool and in the arithmetic, the same products in the same
order in both forms; the sum over ``state`` may associate differently.

Inside the kernel a head's rows are a ``[head_dim, state]`` tile: ``state`` on
the lanes, ``head_dim`` on the sublanes. ``B`` and ``C`` are rows of it
(broadcast over sublanes as they are loaded). ``dt x`` is a column, one value
a sublane, and so is ``y``: both cross the kernel's edge transposed, ``[B,
head_dim, heads]`` with a head a lane, and a head's column is taken out of
(put into) its lane by a select -- the relayout of a row into a column costs
more than the whole update (my chip runs, PR 35). ``keep`` comes replicated
over the lanes, a row a head. The heads of a block run in a rolled loop of
:data:`HEADS_A_TURN` heads a turn: one head a turn leaves the vector units
waiting on each reduction over lanes (7.4 ms for the cell's five layers of 64
lanes; 4.2 ms from two heads a turn on, which is what copying the rows through
VMEM takes with no arithmetic at all; my chip runs, PR 35), and every head
unrolled is traced and compiled again for every lane bucket at every start.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, SUBLANES = 128, 8

# What the kernel may keep in VMEM (of a v5e's 128 MiB): a block of the pool
# in and out, each double-buffered by the pipeline. The compiler's default
# scoped limit of 16 MiB is raised to what the block needs.
VMEM_BUDGET_BYTES = 40 * 2 ** 20

# Heads a turn of the kernel's loop (see the module's docstring).
HEADS_A_TURN = 4


def use_kernel(state: int, head_dim: int, *, platform: str, sharded: bool,
               interpret: bool = False) -> bool:
    """Whether a decode step updates its slots' rows in place in the pool
    (True: the kernel) or gathers, computes and scatters them (False). One
    rule, from what is known when the program is traced: the kernel serves
    where it compiles and wins -- a real TPU (or the interpreter, for tests),
    a pool whole on one device, a head's tile ``[head_dim, state]`` (a
    Mamba-1 slot's ``[state, channels]``: the minor dim first) made of whole
    (8, 128) float32 tiles."""
    if sharded or not (platform == "tpu" or interpret):
        return False
    return state % LANES == 0 and head_dim % SUBLANES == 0


def pick_head_block(heads: int, head_dim: int, state: int) -> int:
    """Heads of one block ``[block, head_dim, state]`` float32: the largest
    divisor of ``heads`` that fits the VMEM budget in and out, double-buffered
    -- the fewer grid steps the better, each has a fixed cost."""
    head_bytes = head_dim * state * 4
    fits = [hb for hb in range(1, heads + 1)
            if heads % hb == 0 and 4 * hb * head_bytes <= VMEM_BUDGET_BYTES]
    if not fits:
        raise ValueError(
            f"ssm state update: one head's [{head_dim}, {state}] float32 rows "
            f"do not fit {VMEM_BUDGET_BYTES >> 20} MiB of VMEM four times")
    return fits[-1]


def update_rows(rows: jax.Array, keep: jax.Array, dtx: jax.Array,
                b: jax.Array, c: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The recurrence on gathered rows, as written: ``rows`` [B, heads,
    head_dim, state] f32, ``keep`` [B, heads] (exp(dt A)), ``dtx`` [B, heads,
    head_dim] (dt x), ``b`` and ``c`` [B, groups, state] (head i reads group
    i // (heads / groups)). Returns (the new rows, y [B, heads, head_dim])."""
    B, H, P, N = rows.shape
    G = b.shape[1]
    R = H // G
    s = rows.reshape(B, G, R, P, N)
    s = (s * keep.reshape(B, G, R)[..., None, None]
         + dtx.reshape(B, G, R, P)[..., None] * b[:, :, None, None, :])
    y = jnp.einsum("bgrpn,bgn->bgrp", s, c)
    return s.reshape(B, H, P, N), y.reshape(B, H, P)


def _update_kernel(layer, slots, keep_ref, dtx_ref, b_ref, c_ref, s_ref,
                   s_out, y_ref, *, heads_per_group: int, turn: int):
    """One (lane, head block) grid step: the block's heads, ``turn`` a turn of
    a rolled loop. keep_ref [heads, state] (a head's value on every lane),
    dtx_ref and y_ref [head_dim, heads] (a head a lane), b_ref and c_ref
    [groups, state], s_ref and s_out [block, head_dim, state]."""
    del layer, slots  # read by the index maps
    hb = s_ref.shape[0]
    j = pl.program_id(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, dtx_ref.shape, 1)

    def heads(t, y_acc):
        for u in range(turn):
            h = t * turn + u                # in the block
            head = j * hb + h               # in the layer
            at = lane == head
            group = pl.ds(head // heads_per_group, 1)
            # The head's column of dt x: every other lane adds a zero.
            col = jnp.sum(jnp.where(at, dtx_ref[...], 0.0), axis=1,
                          keepdims=True)                        # [P, 1]
            s = (s_ref[h] * keep_ref[pl.ds(head, 1), :]
                 + col * b_ref[group, :])
            s_out[h] = s
            y = jnp.sum(s * c_ref[group, :], axis=1, keepdims=True)
            y_acc = jnp.where(at, y, y_acc)
        return y_acc

    y_acc = jax.lax.fori_loop(0, hb // turn, heads,
                              jnp.zeros(y_ref.shape, jnp.float32))

    # The lane's y block stays in VMEM over its head blocks, each of which
    # holds its own heads' lanes and zeros elsewhere.
    @pl.when(j == 0)
    def _first():
        y_ref[...] = y_acc

    @pl.when(j != 0)
    def _add():
        y_ref[...] += y_acc


def update_in_place(ssm: jax.Array, layer: jax.Array, slots: jax.Array,
                    keep: jax.Array, dtx: jax.Array, b: jax.Array,
                    c: jax.Array, *, head_block: int | None = None,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """The recurrence on rows ``slots`` [B] of layer ``layer`` (an int32
    scalar) of the stacked pool ``ssm`` [layers, rows, heads, head_dim, state]
    f32, in place: the result is the pool, aliased to the argument, with
    those rows updated and every other row untouched, and y [B, heads,
    head_dim]. The small operands are :func:`update_rows`'s. A slot named
    twice (padding lanes all name nobody's) is read stale or fresh and
    written in any order. ``head_block`` is the microbench's and the tests'
    to set; served, it comes from the shapes (:func:`pick_head_block`)."""
    _, _, H, P, N = ssm.shape
    B, G = slots.shape[0], b.shape[1]
    hb = head_block or pick_head_block(H, P, N)
    turn = next(t for t in (HEADS_A_TURN, 2, 1) if hb % t == 0)

    def by_lane(*block):
        return pl.BlockSpec((None, *block), lambda i, j, layer, slots:
                            (i,) + (0,) * len(block))

    pool = pl.BlockSpec((None, None, hb, P, N), lambda i, j, layer, slots:
                        (layer[0], slots[i], j, 0, 0))
    new, y = pl.pallas_call(
        functools.partial(_update_kernel, heads_per_group=H // G, turn=turn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H // hb),
            in_specs=[by_lane(H, N), by_lane(P, H), by_lane(G, N),
                      by_lane(G, N), pool],
            out_specs=[pool, by_lane(P, H)]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((B, P, H), jnp.float32)],
        # Operand 6 of the call (the two scalars count): the pool.
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * hb * P * N * 4 + 8 * 2 ** 20),
        interpret=interpret,
        # The op's name in a device trace, for whoever reduces one.
        name="ssm_state_update",
    )(layer.reshape(1).astype(jnp.int32), slots.astype(jnp.int32),
      jnp.broadcast_to(keep[..., None], (B, H, N)), jnp.swapaxes(dtx, 1, 2),
      b, c, ssm)
    return new, jnp.swapaxes(y, 1, 2)


# ---- Mamba-1: a decay a channel a state value -------------------------------
#
# A Mamba-1 layer (models/mamba1.py) keeps ``S [state, channels]`` a sequence:
# the channels (5,120 at Jamba2-3B's widths) on the lanes, the 16 state
# values on the sublanes, so that a tile is whole (8, 128) float32 tiles. Its
# decay is no scalar a head: ``S[n, c] <- exp(dt[c] A[n, c]) S[n, c] + dt[c]
# x[c] B[n]``, ``y[c] = sum_n S[n, c] C[n] + D[c] x[c]``. ``dt`` and ``x`` are
# rows of the tile (broadcast over sublanes), ``B`` and ``C`` columns of it
# (one value a sublane: they cross the kernels' edges as columns), ``A`` a
# tile of the layer's own, and the exponential is taken inside the kernels.
# Two kernels, each beside its plain form:
#
# - :func:`update1_in_place` (op ``ssm1_state_update``): one decode step on
#   the slots' rows of the pool where they lie, as :func:`update_in_place`
#   does it; ``A`` and ``D`` keep one block index over the grid and are
#   fetched once a call. Plain form :func:`update1_rows`.
# - :func:`selective_scan` (op ``ssm1_selective_scan``): a window of a
#   prompt. There is no matrix form (the decay differs by channel AND state
#   value), so the rows go in order, the state tile of a channel block
#   resident in VMEM over all of them; XLA's associative scan would write
#   and read the pair (exp(dt A), dt B x), 2 x 327,680 B a token a layer,
#   several times over. A padding row comes with ``dt`` 0: no decay and no
#   input, so the state a bucket leaves is its last real row's. Plain form
#   :func:`scan_rows` (``lax.scan`` over positions).


def update1_rows(rows: jax.Array, dt: jax.Array, x: jax.Array, b: jax.Array,
                 c: jax.Array, a: jax.Array, d: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    """One step on gathered rows, as written: ``rows`` [B, state, channels]
    f32, ``dt`` (the step sizes) and ``x`` [B, channels], ``b`` and ``c``
    [B, state], ``a`` [state, channels] (negative), ``d`` [channels], all
    f32. Returns (the new rows, y [B, channels])."""
    s = (rows * jnp.exp(dt[:, None, :] * a)
         + (dt * x)[:, None, :] * b[:, :, None])
    return s, jnp.sum(s * c[:, :, None], axis=1) + d * x


def scan_rows(dt: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array,
              a: jax.Array, d: jax.Array, s0: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """A run of positions a sequence, one :func:`update1_rows` a position:
    ``dt`` and ``x`` [B, T, channels], ``b`` and ``c`` [B, T, state], from
    ``s0`` [B, state, channels]. Returns (y [B, T, channels], the state
    after the last row)."""

    def row(s, at):
        s, y = update1_rows(s, *at, a, d)
        return s, y

    s1, y = jax.lax.scan(row, s0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (dt, x, b, c)))
    return jnp.moveaxis(y, 0, 1), s1


def _update1_kernel(layer, slots, dtx_ref, bc_ref, a_ref, d_ref, s_ref,
                    s_out, y_ref):
    """One (lane, channel block) grid step. dtx_ref [2, block] (dt, x),
    bc_ref [2, state, 1] (B, C as columns), a_ref [state, block], d_ref
    [1, block], s_ref and s_out [state, block], y_ref [1, block]."""
    del layer, slots  # read by the index maps
    dt, x = dtx_ref[0:1, :], dtx_ref[1:2, :]
    s = s_ref[...] * jnp.exp(dt * a_ref[...]) + (dt * x) * bc_ref[0]
    s_out[...] = s
    y_ref[...] = (jnp.sum(s * bc_ref[1], axis=0, keepdims=True)
                  + d_ref[...] * x)


def update1_in_place(ssm: jax.Array, layer: jax.Array, slots: jax.Array,
                     dt: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array,
                     a: jax.Array, d: jax.Array, *,
                     channel_block: int | None = None,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """:func:`update1_rows` on rows ``slots`` [B] of layer ``layer`` (an
    int32 scalar) of the stacked pool ``ssm`` [layers, rows, state, channels]
    f32, in place: the result is the pool, aliased to the argument, with
    those rows updated and every other row untouched, and y [B, channels].
    ``channel_block`` is the microbench's and the tests' to set; served, a
    lane's whole tile is one block (320 KB at 16 x 5,120)."""
    _, _, N, C = ssm.shape
    B = slots.shape[0]
    cb = channel_block or C

    def by_lane(*block):
        return pl.BlockSpec((None, *block), lambda i, j, layer, slots:
                            (i,) + (0,) * (len(block) - 1) + (j,))

    pool = pl.BlockSpec((None, None, N, cb), lambda i, j, layer, slots:
                        (layer[0], slots[i], 0, j))
    new, y = pl.pallas_call(
        _update1_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, C // cb),
            in_specs=[by_lane(2, cb),
                      pl.BlockSpec((None, 2, N, 1),
                                   lambda i, j, layer, slots: (i, 0, 0, 0)),
                      pl.BlockSpec((N, cb), lambda i, j, layer, slots: (0, j)),
                      pl.BlockSpec((1, cb), lambda i, j, layer, slots: (0, j)),
                      pool],
            out_specs=[pool, by_lane(1, cb)]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((B, 1, C), jnp.float32)],
        # Operand 6 of the call (the two scalars count): the pool.
        input_output_aliases={6: 0},
        interpret=interpret,
        # The op's name in a device trace, for whoever reduces one.
        name="ssm1_state_update",
    )(layer.reshape(1).astype(jnp.int32), slots.astype(jnp.int32),
      jnp.stack([dt, x], axis=1), jnp.stack([b, c], axis=1)[..., None],
      a, d[None], ssm)
    return new, y[:, 0]


# Rows of a window a grid step of the scan kernel takes: a row's ``B`` and
# ``C`` are picked out of a [state, rows] tile by a select over its lanes, so
# one lane tile of rows is the cheapest.
SCAN_TIME_BLOCK = 128
# Channels of its resident state tile: [16, 1024] f32 is 16 vector
# registers, carried through the loop over rows.
SCAN_CHANNEL_BLOCK = 1024


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, d_ref, s0_ref, y_ref,
                 s1_ref):
    """One (sequence, channel block, block of rows) grid step, the blocks of
    rows in order: dt_ref, x_ref and y_ref [rows, block], b_ref and c_ref
    [state, rows] (a row a lane), a_ref [state, block], d_ref [1, block],
    s0_ref and s1_ref [state, block]; s1_ref stays in VMEM over the blocks
    of rows and is the state between them."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s1_ref[...] = s0_ref[...]

    a, d = a_ref[...], d_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)

    def row(t, s):
        at = lane == t
        # The row's column of B and of C: every other lane adds a zero.
        b = jnp.sum(jnp.where(at, b_ref[...], 0.0), axis=1, keepdims=True)
        c = jnp.sum(jnp.where(at, c_ref[...], 0.0), axis=1, keepdims=True)
        dt, x = dt_ref[pl.ds(t, 1), :], x_ref[pl.ds(t, 1), :]
        s = s * jnp.exp(dt * a) + (dt * x) * b
        y_ref[pl.ds(t, 1), :] = (jnp.sum(s * c, axis=0, keepdims=True)
                                 + d * x)
        return s

    s1_ref[...] = jax.lax.fori_loop(0, dt_ref.shape[0], row, s1_ref[...])


def _block_of(size: int, want: int, unit: int) -> int:
    """The largest divisor of ``size`` that is a multiple of ``unit`` and at
    most ``want``; ``size`` itself where there is none (a block that spans
    its whole dim needs no alignment)."""
    fits = [n for n in range(unit, min(size, want) + 1, unit)
            if size % n == 0]
    return fits[-1] if fits else size


def selective_scan(dt: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array,
                   a: jax.Array, d: jax.Array, s0: jax.Array, *,
                   channel_block: int | None = None,
                   time_block: int | None = None,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """:func:`scan_rows` as one kernel (its operands and results): a program
    a (sequence, channel block), the state tile resident over the window's
    rows. ``b`` and ``c`` cross the edge transposed, [B, state, T]."""
    B, T, C = dt.shape
    N = a.shape[0]
    cb = channel_block or _block_of(C, SCAN_CHANNEL_BLOCK, LANES)
    tb = time_block or _block_of(T, SCAN_TIME_BLOCK, LANES)
    rows = pl.BlockSpec((None, tb, cb), lambda i, j, k: (i, k, j))
    cols = pl.BlockSpec((None, N, tb), lambda i, j, k: (i, 0, k))
    tile = pl.BlockSpec((None, N, cb), lambda i, j, k: (i, 0, j))
    y, s1 = pl.pallas_call(
        _scan_kernel,
        grid=(B, C // cb, T // tb),
        in_specs=[rows, rows, cols, cols,
                  pl.BlockSpec((N, cb), lambda i, j, k: (0, j)),
                  pl.BlockSpec((1, cb), lambda i, j, k: (0, j)), tile],
        out_specs=[rows, tile],
        out_shape=[jax.ShapeDtypeStruct((B, T, C), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm1_selective_scan",
    )(dt, x, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2), a, d[None], s0)
    return y, s1
