"""The state pool: what a state-space layer keeps a sequence, beside the pages.

A Mamba-2 layer (models/hybrid.py) keeps, for every sequence, a state of
``heads x head_dim x state`` values and the last ``conv - 1`` inputs of its
convolution: a fixed size whatever the context. That is no page of tokens, so
it does not live in the page pool. It lives in a pool **indexed by engine
slot**:

- ``ssm``  ``[state layers, slots + 1, heads, head_dim, state]`` float32;
- ``conv`` ``[state layers, slots + 1, (conv - 1) * channels]`` in the model's
  dtype, a slot's rows flat so that the minor dim is whole lanes.

Row ``slots`` (the engine's ``max_batch``) is nobody's: padding lanes and
warm-up programs read and write it, as padded page writes go to the trash
block.

A Mamba-1 layer (models/hybrid.py's "S" layers) keeps another kind of state:
no heads, a decay of its own for every channel and state value. Its pool is
the SECOND LAYOUT, under the same slots, owner and functions:

- ``ssm``  ``[state layers, slots + 1, state, inner]`` float32: the channels
  (5,120 at Jamba2-3B's widths) on the lanes and the 16 state values on the
  sublanes, where 40 "heads" of ``[128, 16]`` would put 16 values on 128
  lanes;
- ``conv`` ``[state layers, slots + 1, (conv - 1) * inner]``: the
  convolution there runs over x alone.

``StateGeometry.inner`` says which layout a pool has; a decode step of such
a layer goes through :func:`recur1`, whose kernel takes the exponential of
``dt A`` itself from the layer's own ``A`` tile.

A gated short convolution (models/hybrid.py's "C" layers, the lfm2_moe
family) keeps the THIRD KIND of slot row: the tail and nothing else.

- ``ssm``  None: there is no recurrent state (``StateGeometry.state`` 0, an
  empty ``row_shape``), no :func:`recur` / :func:`recur1` call and no kernel
  of ops/pallas_ssm.py;
- ``conv`` ``[state layers, slots + 1, (conv - 1) * d_model]`` in the model's
  dtype: the last two rows of ``B * u`` at LFM2-8B-A1B's convolution of 3,
  8,192 B a slot a layer.

The slots, the owner and :func:`start` / :func:`read` / :func:`write` are the
same; where they take or give a state, such a pool takes and gives None.

Who writes a slot's rows. A request's first prefill window writes them whole
(it starts from zeros and never reads them: :func:`start`); a later window
reads them (:func:`read`) and writes every layer's back at its end
(:func:`write`); a decode step of a lane in that slot updates its state a
layer, when the layer runs (:func:`recur`), and writes the tails at the
step's end; nobody else touches them. The device's in-order stream is the
guarantee: a lane that overshoots a finished request writes its own slot
only, and the next request's first window, dispatched later, overwrites it.

How a decode step fetches its rows of ``ssm`` is :func:`recur`'s to choose,
by what the caller says the program traced with (ops/pallas_ssm.use_kernel):
the kernel updates the slots' rows in place in the pool, a slot's 4 MB a
layer once into VMEM and once back; the plain form gathers them by slot,
computes and scatters them back, the CPU's way and the form the kernel is
tested against. A real slot appears at most once in a step, so the update in
place has no hazard between lanes; padding lanes all name nobody's slot and
may read it stale and write it in any order. The tail (61 KB a slot a layer
at Nemotron-3-Super's widths, 1.4% of the bytes) is gathered and scattered by
XLA in both.

A model with state layers hands its whole cache through the step functions as
one value, :class:`Cache`, where the other families hand ``(k_pages,
v_pages)``: the pair becomes ``(cache, None)``. Besides the pools it carries
small things that ride with a step: ``slots`` [B], the engine slot of
every row of this step (set by the engine before the call, :func:`at_slots`),
and the counts its programs sum on the device, which the engine takes out
after the call (:func:`take_counts`): ``held``, the (token, expert) choices
this step's programs found to live on this chip, ``zero``, those that
named an expert that computes nothing, and -- only where the engine says the
step's program counts them (``at_slots(..., reads=True)``: a decode chunk in
the form that reads the chosen experts alone, ops/pallas_moe.chosen_experts)
-- ``read``, the held experts whose weights its expert layers read.
``engine/core.py`` learns none of these shapes.

The counts have this one carrier in every family. A latent page pool whose
model counts its router's choices (models/mla.py, where a chip holds a share
of the experts or the router has zero-compute outputs:
``ModelConfig.tallies_choices``) rides in a :class:`Cache` as well: ``k`` the
latent pool, ``v`` None, and no state pools (``ssm`` and ``conv`` None). Where
that model's block selects rows, the indexer's key pool (kvcache/pages.py)
rides there too, as ``idx``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import pallas_ssm


# What an engine whose model has state-space layers turns off, as /health
# lists it (``settings.off_for_state_layers``).
OFF_FOR_STATE_LAYERS = (
    "prefix hits (a cached block prefix has no recurrent state to start from)",
    "kv events (no block is advertised to a router)",
    "tp/ep/pp and multi-process meshes",
    "roles other than both",
    "KV export and import")


@dataclasses.dataclass(frozen=True)
class StateGeometry:
    """What the state pool's shapes follow from."""

    n_layers: int       # state-space layers
    n_slots: int        # engine slots; the pool holds one more, nobody's
    heads: int
    head_dim: int
    state: int
    tail_rows: int      # conv - 1 inputs kept
    channels: int       # what the convolution runs over
    dtype: str          # of the tail; the state is float32
    # The second layout, a Mamba-1 layer's: ``[state, inner]`` a slot a
    # layer, the channels on the lanes (no heads: ``heads`` and ``head_dim``
    # 0); 0: the layout by heads.
    inner: int = 0

    @classmethod
    def for_engine(cls, model: Any, max_batch: int) -> "StateGeometry | None":
        """The engine's pool for ``model`` (anything with n_state_layers and
        the ssm_* widths); None for a model that keeps pages alone."""
        if not getattr(model, "n_state_layers", 0):
            return None
        if not model.ssm_row:
            # A tail and nothing else (a gated short convolution).
            return cls(model.n_state_layers, max_batch, 0, 0, 0,
                       model.ssm_conv - 1, model.ssm_conv_dim,
                       str(jnp.dtype(model.dtype)))
        if getattr(model, "ssm_dt_rank", 0):
            return cls(model.n_state_layers, max_batch, 0, 0, model.ssm_state,
                       model.ssm_conv - 1, model.ssm_conv_dim,
                       str(jnp.dtype(model.dtype)), inner=model.ssm_inner)
        return cls(model.n_state_layers, max_batch, model.ssm_heads,
                   model.ssm_head_dim, model.ssm_state, model.ssm_conv - 1,
                   model.ssm_conv_dim, str(jnp.dtype(model.dtype)))

    @property
    def row_shape(self) -> tuple[int, ...]:
        """One sequence's recurrent state in one layer; empty where a layer
        keeps its convolution's tail alone."""
        if not self.state:
            return ()
        if self.inner:
            return (self.state, self.inner)
        return (self.heads, self.head_dim, self.state)

    @property
    def ssm_shape(self) -> tuple[int, ...] | None:
        """The recurrent states' pool; None where there is none."""
        if not self.row_shape:
            return None
        return (self.n_layers, self.n_slots + 1, *self.row_shape)

    @property
    def conv_shape(self) -> tuple[int, ...]:
        return (self.n_layers, self.n_slots + 1,
                self.tail_rows * self.channels)

    @property
    def slot_bytes(self) -> int:
        """Bytes one sequence keeps, every state layer."""
        ssm = int(np.prod(self.row_shape)) * 4 if self.row_shape else 0
        tail = self.tail_rows * self.channels * jnp.dtype(self.dtype).itemsize
        return self.n_layers * (ssm + tail)

    @property
    def pool_bytes(self) -> int:
        return (self.n_slots + 1) * self.slot_bytes


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Cache:
    """Pages and state as one value (see the module's docstring)."""

    k: jax.Array                    # the K/V page pools (kvcache/pages.py),
    v: jax.Array | None             # or a latent pool and None
    ssm: jax.Array | None           # None: no layer keeps a recurrent state
    conv: jax.Array | None          # None: a model without state layers
    slots: jax.Array | None = None  # [B] int32: the rows of this step
    held: jax.Array | None = None   # int32 scalar: choices held, this step
    zero: jax.Array | None = None   # the same of zero-compute choices,
    # which only a model whose router has such outputs counts.
    counts_zero: bool = dataclasses.field(default=False,
                                          metadata=dict(static=True))
    read: jax.Array | None = None   # held experts read, where a step counts
    idx: jax.Array | None = None    # the indexer's key pool beside a latent k
    # The latent pool of the layers that keep a window of the context
    # (kvcache/pages.py), or their K pool and, in ``win_v``, their V pool;
    # and this step's tables of it [B, table width], which ride with the step
    # as ``slots`` do.
    win: jax.Array | None = None
    wt: jax.Array | None = None
    win_v: jax.Array | None = None
    # Whether the model's programs count their router's choices at all
    # (``PageGeometry.counted``): a cache that is this value for its window
    # pools' sake alone carries no count.
    counted: bool = dataclasses.field(default=True,
                                      metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Fresh:
    """What a first prefill window hands to ``pages.write_sequences``: its
    K/V rows [attention layers, B, S, Hkv, D], and for every sequence the
    state [state layers, B, heads, head_dim, state] (None where the layers
    keep a tail alone) and the tail [state layers, B, tail rows, channels]
    its true last token left."""

    k: jax.Array
    v: jax.Array | None
    ssm: jax.Array | None
    conv: jax.Array | None
    held: jax.Array
    zero: jax.Array | None = None
    idx: jax.Array | None = None    # the rows' indexer keys [L, B, S, width]
    win: jax.Array | None = None    # the window layers' rows [Lw, B, S, width],
    win_v: jax.Array | None = None  # or their K and V [Lw, B, S, Hkv, D] each


def alloc(geom: StateGeometry | None, k_pages: jax.Array,
          v_pages: jax.Array | None, *, device=None,
          counts_zero: bool = False, idx: jax.Array | None = None,
          win: jax.Array | None = None, win_v: jax.Array | None = None,
          counted: bool = True) -> Cache:
    """A zeroed state pool beside the given page pools; ``geom`` None: the
    page pools alone (with ``idx``, an indexer's key pool beside a latent
    one; with ``win``, the window layers' pool, or with ``win_v`` their
    pair), in the value that carries a step's counts (``counted``)."""
    if geom is None:
        return Cache(k_pages, v_pages, None, None, counts_zero=counts_zero,
                     idx=idx, win=win, win_v=win_v, counted=counted)
    return Cache(k_pages, v_pages,
                 None if geom.ssm_shape is None else
                 jnp.zeros(geom.ssm_shape, jnp.float32, device=device),
                 jnp.zeros(geom.conv_shape, jnp.dtype(geom.dtype),
                           device=device), counts_zero=counts_zero)


def at_slots(cache: Any, slots: Any, wt: Any = None, *, reads: bool = False
             ) -> Any:
    """``cache`` as a step on rows ``slots`` takes it (``slots`` from the
    host: the step donates its cache, and what rides in it goes with it),
    with the step's window tables ``wt`` where it keeps a window pool, and
    ``reads`` where its program counts the held experts it read; anything
    that is no :class:`Cache` (a page pool) goes through as it is."""
    if not isinstance(cache, Cache):
        return cache
    return dataclasses.replace(
        cache, slots=np.asarray(slots, np.int32),
        held=np.zeros((), np.int32) if cache.counted else None,
        zero=np.zeros((), np.int32) if cache.counts_zero else None,
        read=np.zeros((), np.int32) if reads else None,
        wt=None if wt is None else np.asarray(wt, np.int32))


def take_counts(cache: Any) -> tuple[Any, jax.Array | None,
                                     jax.Array | None, jax.Array | None]:
    """(The cache as it is kept between steps, the step's count of held
    expert choices or None, its count of zero-compute choices or None, its
    count of held experts read or None.) The counts leave the cache so that
    they are not donated to the next step with it."""
    if not isinstance(cache, Cache):
        return cache, None, None, None
    return (dataclasses.replace(cache, slots=None, held=None, zero=None,
                                read=None, wt=None),
            cache.held, cache.zero, cache.read)


def counted(cache: Cache, held: jax.Array, zero: jax.Array | None = None,
            read: jax.Array | None = None) -> Cache:
    """``cache`` with a program's counts added to those it carries."""
    if cache.held is None:
        return cache
    return dataclasses.replace(
        cache, held=cache.held + held,
        zero=None if cache.zero is None else cache.zero + zero,
        read=(cache.read if read is None or cache.read is None
              else cache.read + read))


# ---- reads and writes, by slot ---------------------------------------------------


def read(cache: Cache, layer: int) -> tuple[jax.Array, jax.Array]:
    """State layer ``layer`` of this step's rows: (state [B, heads, head_dim,
    state] f32 -- [B, state, inner] in the second layout, None where the pool
    keeps tails alone --, tail [B, tail rows * channels], the rows flat as
    stored)."""
    return (None if cache.ssm is None else cache.ssm[layer, cache.slots],
            tail(cache, layer))


def tail(cache: Cache, layer: int) -> jax.Array:
    """The convolution's tail of this step's rows at state layer ``layer``
    [B, tail rows * channels], the rows flat as stored: what a decode step
    gathers (its states stay where they are, :func:`recur`)."""
    return cache.conv[layer, cache.slots]


def recur(cache: Cache, layer: int, keep: jax.Array, dtx: jax.Array,
          b: jax.Array, c: jax.Array, *, impl: str = "gathered"
          ) -> tuple[Cache, jax.Array]:
    """One step of the recurrence on this step's rows of state layer
    ``layer``: ``S <- S * keep + dtx (x) b``, ``y = S c`` (the operands as
    ops/pallas_ssm.update_rows takes them, a row a lane). Returns (the cache
    with those rows updated, y [B, heads, head_dim]). ``impl`` is the form
    the program traces with: "kernel" (in place in the pool;
    "kernel_interpret" the same through the interpreter, for tests on the
    CPU) or "gathered"."""
    if impl.startswith("kernel"):
        ssm, y = pallas_ssm.update_in_place(
            cache.ssm, jnp.asarray(layer, jnp.int32), cache.slots, keep, dtx,
            b, c, interpret=impl == "kernel_interpret")
    else:
        rows, y = pallas_ssm.update_rows(cache.ssm[layer, cache.slots], keep,
                                         dtx, b, c)
        ssm = cache.ssm.at[layer, cache.slots].set(rows)
    return dataclasses.replace(cache, ssm=ssm), y


def recur1(cache: Cache, layer: int, dt: jax.Array, x: jax.Array,
           b: jax.Array, c: jax.Array, a: jax.Array, d: jax.Array, *,
           impl: str = "gathered") -> tuple[Cache, jax.Array]:
    """:func:`recur` for the second layout (a Mamba-1 layer's rows, ``[state,
    inner]``): ``S <- exp(dt A) S + dt x (x) b``, ``y = S c + d x``, the
    operands as ops/pallas_ssm.update1_rows takes them. Returns (the cache
    with those rows updated, y [B, inner]); ``impl`` as there."""
    if impl.startswith("kernel"):
        ssm, y = pallas_ssm.update1_in_place(
            cache.ssm, jnp.asarray(layer, jnp.int32), cache.slots, dt, x, b,
            c, a, d, interpret=impl == "kernel_interpret")
    else:
        rows, y = pallas_ssm.update1_rows(cache.ssm[layer, cache.slots], dt,
                                          x, b, c, a, d)
        ssm = cache.ssm.at[layer, cache.slots].set(rows)
    return dataclasses.replace(cache, ssm=ssm), y


def write(cache: Cache, ssm: list[jax.Array] | None, conv: list[jax.Array]
          ) -> Cache:
    """Every state layer's new rows, in layer order (``ssm[l]`` [B, heads,
    head_dim, state], ``conv[l]`` [B, tail rows, channels]), into this step's
    slots: one scatter a pool. ``ssm`` None: the states were updated a layer
    (:func:`recur`), or there are none, and the tails alone are written.
    Padding rows all name nobody's slot, and which of them lands there is
    nobody's concern."""
    B = cache.slots.shape[0]
    new_conv = jnp.stack(conv).reshape(len(conv), B, -1).astype(
        cache.conv.dtype)
    cache = dataclasses.replace(
        cache, conv=cache.conv.at[:, cache.slots].set(new_conv))
    if ssm is None:
        return cache
    new_ssm = jnp.stack(ssm).astype(cache.ssm.dtype)
    return dataclasses.replace(
        cache, ssm=cache.ssm.at[:, cache.slots].set(new_ssm))


def start(cache: Cache, fresh: Fresh, k_pages: jax.Array,
          v_pages: jax.Array) -> Cache:
    """``cache`` after a first prefill window: the page pools as the window's
    K/V write left them, and this step's slots started afresh from
    ``fresh``."""
    cache = counted(dataclasses.replace(cache, k=k_pages, v=v_pages),
                    fresh.held, fresh.zero)
    if cache.conv is None:
        return cache
    return write(cache, None if fresh.ssm is None else list(fresh.ssm),
                 list(fresh.conv))
