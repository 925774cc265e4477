"""The page pool: its shape, where it lives, and every read and write of it.

``k_pages`` and ``v_pages`` are each ``[n_layers, n_blocks, block, n_kv_heads,
head_dim]``: every layer's pool stacked, a page (block) of ``block`` tokens,
a token's KV heads side by side. Block 0 is the trash block: no sequence
owns it, and every padded or masked-off write is pointed at it so that the
scatters keep static shapes with no branch.

**Heads narrower than a lane.** A bf16 array's minor dim is stored in tiles
of 128 lanes: in the row-major layout a kernel's operand has, a pool ``[..,
block, 8, 64]`` lies padded to ``[.., 8, 128]`` in HBM whatever is written
there (twice the model's bytes: tests/test_chip_compile.py; left to itself
the compiler lays such a pool out with the BLOCKS minor, which no kernel
can read), and the decode kernel's page copies want whole lanes. Where the
model says so
(``ModelConfig.kv_heads_a_row`` > 1: models/hybrid.py's lfm2_moe family, 8 KV
heads of 64) a page keeps that many ADJACENT KV heads side by side as one row
of 128: ``[.., block, n_kv_heads / 2, 128]``, which is the same bytes in the
same order as ``[.., block, n_kv_heads, 64]`` -- so every write and read here
takes and gives the model's own ``[.., Hkv, D]`` rows and reshapes, and
``kv_token_bytes`` is the model's (2,048 at 8 x 64 in bf16). The decode walk
over such pages is the kernel of half as many heads twice as wide
(:func:`decode_attention`).

The pools stay stacked and are read at (layer, page) (PERF.md, PR 26: a pool
scanned over reaches the Pallas kernel as one layer's slice, which XLA copies
out first). The page is 16 tokens and the kernel picks its own stage from the
shapes (PR 29).

A second kind of page holds latent attention's cache (models/mla.py): ONE
pool ``[n_layers, n_blocks, block, row_width]``, a token's row its
``latent_dim`` values (the normed latent, then the rotated key part) shared
by every head, padded with zeros to whole lanes (``row_width``: 576 -> 640)
because a TPU array's minor dim is stored in tiles of 128 anyway and the
kernel's DMA wants the page's rows to be whole tiles. Wherever this module
takes or returns a ``(k_pages, v_pages)`` pair, a latent pool is the pair
``(pool, None)``: there is no second array.

A third kind lies beside a latent pool where the block selects rows
(DeepSeek-V3.2's indexer, models/mla.py): the indexer's key pool
``[n_layers, n_blocks, block, index_dim]``, a token's ``index_dim`` values a
layer (128: whole lanes as they are), under the SAME block ids — one block
table, one allocator, so a page of it is allocated, freed, parked in the
prefix cache and found again with the latent page of that id. It is a pool of
its own, not more columns of the latent row, so that the indexer reads its
256 B a token without touching the 1,280 B beside them. It has the latent
kind's layout at another width, so the latent kind's writes and reads serve
it (:func:`write`, :func:`write_sequences` with ``v_pages`` None,
:func:`read_latent_prefix`, :func:`read_rows`); the pair of them rides in a
``kvcache/state.Cache`` (``k`` the latent pool, ``idx`` this one).

A fourth kind is the latent pool of layers that attend to a WINDOW of the
context (models/mla.py, ``ModelConfig.window_attn``): ``[window layers,
n_blocks, block, row_width]`` at those layers' own row width, with block ids
and a block table of its own (:class:`WindowGeometry`). A request holds the
pages its window reaches and no others: the owner (engine/blocks.py) gives a
page back once every row of it lies more than ``window - 1`` behind the
request's position, so the pool's size follows the engine's lanes and not the
context. Its table is indexed by logical page like the other (entry p the page
of positions ``p * block ...``; 0, the trash block, where the request holds
none), rides with a step in the ``kvcache/state.Cache`` (``win`` the pool,
``wt`` the step's tables), and the latent kind's writes and reads serve it.

A fifth kind is the same window pool for K/V attention (models/llama.py,
``ModelConfig.kv_window``): a PAIR of pools ``[window layers, n_blocks, block,
n_kv_heads, head_dim]`` (``win`` and ``win_v`` of the ``state.Cache``) under
the same owner, table and rules, beside the pair of the layers that keep the
whole context; the K/V kind's writes and reads serve it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import (banded_attention,
                             latent_paged_decode_attention,
                             paged_decode_attention,
                             swa_latent_decode_attention,
                             swa_paged_decode_attention)
from ..ops.pallas_dsa import sparse_latent_paged_decode_attention_pallas
from ..ops.pallas_latent_attention import (
    RUN_PAGES, latent_paged_decode_attention_pallas,
    swa_latent_decode_attention_pallas)
from ..ops.pallas_paged_attention import (kv_window_prefill_attention,
                                          paged_decode_attention_pallas,
                                          swa_paged_decode_attention_kernel)
from ..ops.sparse_attention import sparse_latent_paged_decode_attention
from . import state as state_pool

TRASH_BLOCK = 0
LANES = 128

# What an engine whose model has window layers turns off, as /health lists it
# (``settings.off_for_window_layers``).
OFF_FOR_WINDOW_LAYERS = (
    "prefix hits (a cached block prefix has no window rows: they were given "
    "back while the request ran)",
    "tp/ep/pp and multi-process meshes",
    "roles other than both",
    "KV export and import")


def _whole_lanes(values: int) -> int:
    return -(-values // LANES) * LANES


@dataclasses.dataclass(frozen=True)
class WindowGeometry:
    """The pool of the cache layers that keep a window of the context: what
    its shape follows from."""

    n_layers: int
    n_blocks: int            # the trash block among them
    block: int
    latent_dim: int          # values of a row; stored padded to whole lanes
    window: int              # tokens a query sees, its own among them
    lanes: int               # requests that may hold pages at once
    dtype: str
    # A K/V pair of pools (latent_dim 0): a token's KV heads side by side.
    n_kv_heads: int = 0
    head_dim: int = 0

    @classmethod
    def for_engine(cls, model: Any, max_batch: int) -> "WindowGeometry | None":
        """The engine's window pool for ``model`` (anything with
        n_window_layers, window and, for a latent pool, of_window()); None
        for a model without such layers. The pool is handed out in aligned
        stretches of RUN_PAGES pages (engine/blocks.WindowedAllocator).
        Every request that may be live holds ``lane_stretches`` of them at
        most (``max_batch`` slots, and an eighth as many again for requests
        that finish inside the chunk in flight while a successor has their
        slot), and one prefill window's new stretches exist beside its old
        ones for the length of a call: ``(lanes + 1) x lane_stretches``
        stretches and the trash block, whatever ``max_model_len``."""
        if not getattr(model, "n_window_layers", 0):
            return None
        window, block = model.window, model.kv_block_size
        lanes = max_batch + max(2, max_batch // 8)
        latent = model.of_window().latent_dim if model.latent_dim else 0
        return cls(model.n_window_layers,
                   1 + (lanes + 1) * RUN_PAGES
                   * cls.stretches_of(cls.pages_of(window, block)), block,
                   latent, window, lanes, str(jnp.dtype(model.dtype)),
                   n_kv_heads=0 if latent else model.n_kv_heads,
                   head_dim=0 if latent else model.head_dim)

    @staticmethod
    def pages_of(window: int, block: int) -> int:
        """Pages a request holds at most between two steps: the window's
        rows and a decode chunk's, whichever way they lie across pages."""
        return -(-window // block) + 1

    @staticmethod
    def stretches_of(pages: int) -> int:
        """Aligned stretches of RUN_PAGES that ``pages`` pages in a row, and
        one more a decode chunk in flight writes, can lie across."""
        return -(-pages // RUN_PAGES) + 1

    def describe(self, n_full: int) -> dict[str, Any]:
        """The window pool's part of /health's ``settings``, beside the
        ``n_full`` cache layers that keep the whole context."""
        return {
            "kv_layers_full": n_full,
            "kv_layers_window": self.n_layers,
            "window": self.window,
            "window_token_bytes": self.token_bytes,
            "window_pool_bytes": self.pool_bytes,
            "window_blocks": self.n_blocks,
            "off_for_window_layers": list(OFF_FOR_WINDOW_LAYERS)}

    @property
    def lane_pages(self) -> int:
        return self.pages_of(self.window, self.block)

    @property
    def lane_stretches(self) -> int:
        """Stretches (of ``PageGeometry.run_pages`` pages) reserved for a
        request."""
        return self.stretches_of(self.lane_pages)

    @property
    def row_width(self) -> int:
        return _whole_lanes(self.latent_dim)

    @property
    def shape(self) -> tuple[int, ...]:
        """The pool's shape: the latent pool's, or of K and of V each."""
        if self.latent_dim:
            return (self.n_layers, self.n_blocks, self.block, self.row_width)
        return (self.n_layers, self.n_blocks, self.block, self.n_kv_heads,
                self.head_dim)

    @property
    def token_bytes(self) -> int:
        """Bytes a token holds in one window layer (K and V, or its row)."""
        per = self.row_width or 2 * self.n_kv_heads * self.head_dim
        return per * jnp.dtype(self.dtype).itemsize

    @property
    def pool_bytes(self) -> int:
        """Bytes of the latent pool, or of the pair."""
        return self.n_layers * self.n_blocks * self.block * self.token_bytes


@dataclasses.dataclass(frozen=True)
class PageGeometry:
    """What a pool's shape follows from. Built once, from the model's widths
    and either a block count or the engine's batch and context limits."""

    n_layers: int
    n_blocks: int            # the trash block among them
    block: int               # tokens a page
    n_kv_heads: int
    head_dim: int
    dtype: str
    max_blocks_per_seq: int  # a block table's width
    # Values of a latent row; 0 = the K/V pair (n_kv_heads x head_dim each).
    latent_dim: int = 0
    # Values of an indexer key, in a pool of its own beside the latent one;
    # 0 = no such pool.
    index_dim: int = 0
    # Whether the model's step programs count their router's choices
    # (``counts_zero``: the zero-compute ones too): see :func:`alloc`.
    counted: bool = False
    counts_zero: bool = False
    # What state-space layers keep an engine slot beside the pages
    # (kvcache/state.py); None without them, and for no engine's pool.
    state: state_pool.StateGeometry | None = None
    # The pool of the layers that keep a window of the context, with block
    # ids of its own; None without such layers, and for no engine's pool.
    window: WindowGeometry | None = None

    @classmethod
    def for_model(cls, model: Any, n_blocks: int,
                  max_blocks_per_seq: int | None = None,
                  dtype: str | None = None) -> "PageGeometry":
        """A pool of ``n_blocks`` pages at ``model``'s widths (anything with
        n_layers, kv_block_size, n_kv_heads (``kv_heads_kept`` where it
        keeps a head more than once; ``kv_heads_a_row`` where a page row
        holds several), head_dim and dtype; a
        ``latent_dim`` above 0 asks for the latent kind; ``n_kv_layers``
        where not every layer keeps pages; ``tallies_choices`` and
        ``n_zero_experts`` where its programs count). Never fewer than two
        blocks: the trash block and one to use."""
        n_blocks = max(n_blocks, 2)
        # KV heads narrower than a lane lie side by side, a row of 128.
        side = getattr(model, "kv_heads_a_row", 1)
        return cls(getattr(model, "n_kv_layers", model.n_layers), n_blocks,
                   model.kv_block_size,
                   getattr(model, "kv_heads_kept", model.n_kv_heads) // side,
                   model.head_dim * side,
                   str(jnp.dtype(dtype or model.dtype)),
                   max_blocks_per_seq or n_blocks - 1,
                   getattr(model, "latent_dim", 0),
                   getattr(model, "index_dim", 0),
                   bool(getattr(model, "tallies_choices", False)),
                   bool(getattr(model, "n_zero_experts", 0)))

    @classmethod
    def for_engine(cls, model: Any, max_batch: int, max_model_len: int,
                   hbm_kv_blocks: int = 0) -> "PageGeometry":
        """The engine's pool: ``hbm_kv_blocks`` where given, else room for
        every lane at full length beside the trash block; and a state pool
        of ``max_batch`` slots where the model has state-space layers."""
        per_seq = -(-max_model_len // model.kv_block_size)
        return dataclasses.replace(
            cls.for_model(model, hbm_kv_blocks or 1 + max_batch * per_seq,
                          per_seq),
            state=state_pool.StateGeometry.for_engine(model, max_batch),
            window=WindowGeometry.for_engine(model, max_batch))

    @property
    def row_width(self) -> int:
        """A latent row as stored: ``latent_dim`` padded to whole lanes."""
        return _whole_lanes(self.latent_dim)

    @property
    def shape(self) -> tuple[int, ...]:
        """One pool's shape: of K and of V each, or of the latent pool."""
        if self.latent_dim:
            return (self.n_layers, self.n_blocks, self.block, self.row_width)
        return (self.n_layers, self.n_blocks, self.block, self.n_kv_heads,
                self.head_dim)

    @property
    def index_shape(self) -> tuple[int, ...] | None:
        """The indexer's key pool's shape; None where there is none."""
        if not self.index_dim:
            return None
        return (self.n_layers, self.n_blocks, self.block, self.index_dim)

    @property
    def index_token_bytes(self) -> int:
        """Bytes a token's indexer key holds in one layer."""
        return self.index_dim * jnp.dtype(self.dtype).itemsize

    @property
    def index_pool_bytes(self) -> int:
        return (self.n_layers * self.n_blocks * self.block
                * self.index_token_bytes)

    @property
    def token_bytes(self) -> int:
        """Bytes a token holds in one layer, the layout's padding counted."""
        per = self.row_width or 2 * self.n_kv_heads * self.head_dim
        return per * jnp.dtype(self.dtype).itemsize

    def blocks_for(self, tokens: int) -> int:
        return -(-tokens // self.block)

    @property
    def block_bytes(self) -> int:
        """Bytes one block id holds: K and V (or the padded latent rows),
        every layer."""
        return self.n_layers * self.block * self.token_bytes

    @property
    def pool_bytes(self) -> int:
        """Bytes of the pair of pools, or of the one latent pool."""
        return self.n_blocks * self.block_bytes

    @property
    def run_pages(self) -> int:
        """Table entries the paged decode kernels of either family fetch as
        one copy where they name adjacent ascending blocks; and the pages of
        a stretch, the unit a window pool is handed out in."""
        return RUN_PAGES

    @property
    def one_chip_only(self) -> str | None:
        """What this cache keeps that lives on the unsharded one-chip engine
        alone, in words (None: plain K/V pages): a latent pool, a state pool
        and an indexer's key pool have no sharding rule, stage split or wire
        format yet (ROADMAP R7, R8; a selection over sharded keys would need
        every shard's scores)."""
        if self.window:
            return (("a latent (MLA) page pool and a second one"
                     if self.latent_dim else
                     "K/V page pools and a second pair of them")
                    + " of the layers that keep a window of the context, "
                    "under a block table each")
        if self.index_dim:
            return ("a latent (MLA) page pool and its indexer's key pool "
                    "beside it, under one block table")
        if self.latent_dim:
            return "a latent (MLA) page pool"
        if self.state:
            return "a recurrent state pool beside its pages"
        return None

    def describe(self) -> dict[str, Any]:
        """What the cache holds, as /health's ``settings`` say it (every key
        on every engine, but a window pool's, which only an engine that has
        one reports: tests/test_family_seam.py holds the others' keys to
        PR 43's)."""
        return {
            # The cache layers (two a double layer), a token's bytes in one
            # of them, the layout's padding counted, and the whole pool's.
            "kv_layers": self.n_layers,
            "kv_token_bytes": self.token_bytes,
            "kv_pool_bytes": self.pool_bytes,
            "kv_run_pages": self.run_pages,
            # An indexer's key pool (kv_token_bytes stays the latent row's).
            "index_token_bytes": self.index_token_bytes,
            "index_pool_bytes": self.index_pool_bytes,
            # What state-space layers keep a slot, and turn off.
            "state_slot_bytes": self.state.slot_bytes if self.state else 0,
            "state_pool_bytes": self.state.pool_bytes if self.state else 0,
            "off_for_state_layers": (list(state_pool.OFF_FOR_STATE_LAYERS)
                                     if self.state else []),
            **(self.window.describe(self.n_layers) if self.window else {}),
        }


# ---- where a pool lives -----------------------------------------------------


def page_spec(mesh: Mesh) -> P:
    """The sharding rule: layers follow the stage split where the mesh has
    one (``pp``), KV heads follow ``tp``; the block pool is whole on every
    ``dp`` / ``ep`` replica (any lane may reference any block; attention has
    no experts axis). GQA's head grouping stays shard-local because ``tp``
    divides n_kv_heads, so gathers and scatters need no collective."""
    return P("pp" if "pp" in mesh.axis_names else None, None, None, "tp",
             None)


def page_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of a pool, and of blocks gathered out of one: the blocks
    axis, the only one whose size differs, is unsharded in every layout."""
    return NamedSharding(mesh, page_spec(mesh))


def alloc(geom: PageGeometry, *, device=None, sharding=None
          ) -> tuple[Any, jax.Array | None]:
    """Zeroed ``(k_pages, v_pages)``, on one device or laid out by
    ``sharding`` (made in place, shard by shard); ``(pool, None)`` for a
    latent geometry, which has no sharding rule yet. With ``geom.state``,
    ``geom.counted`` or an indexer's key pool (``geom.index_dim``) the pair
    is ``(cache, None)``: the pools, the state pool and a step's counts as
    one value (kvcache/state.py), unsharded too."""
    if (geom.state is not None or geom.counted or geom.index_dim
            or geom.window):
        if sharding is not None or (geom.state is not None
                                    and geom.latent_dim):
            raise ValueError("a state pool lies beside an unsharded K/V page "
                             "pool only, and a step's counts ride with an "
                             "unsharded pool only: no sharding rule for "
                             "either")

        def zeros(shape):
            return (None if shape is None else
                    jnp.zeros(shape, jnp.dtype(geom.dtype), device=device))

        window = geom.window.shape if geom.window else None
        return state_pool.alloc(
            geom.state, *_alloc_pools(geom, device), device=device,
            counts_zero=geom.counts_zero, idx=zeros(geom.index_shape),
            win=zeros(window),
            win_v=zeros(None if geom.latent_dim else window),
            counted=geom.counted), None
    return _alloc_pools(geom, device, sharding)


def _alloc_pools(geom: PageGeometry, device=None, sharding=None
                 ) -> tuple[jax.Array, jax.Array | None]:
    """The K/V pair, or the latent pool and None, alone."""
    dtype = jnp.dtype(geom.dtype)
    if geom.latent_dim:
        if sharding is not None:
            raise ValueError("a latent page pool is not sharded: page_spec "
                             "splits KV heads, and a latent row has none")
        return jnp.zeros(geom.shape, dtype, device=device), None
    if sharding is not None:
        zeros = jax.jit(lambda: jnp.zeros(geom.shape, dtype),
                        out_shardings=sharding)
        return zeros(), zeros()
    return (jnp.zeros(geom.shape, dtype, device=device),
            jnp.zeros(geom.shape, dtype, device=device))


def block_size(k_pages: jax.Array) -> int:
    return k_pages.shape[2]


def layer_indices(k_pages: jax.Array) -> jax.Array:
    """int32 index of every layer the pool holds (a shard_map body sees its
    stage's layers only): what a scan over layers carries beside the
    parameters, since the pools themselves are closed over, not scanned."""
    return jnp.arange(k_pages.shape[0], dtype=jnp.int32)


# ---- writes -------------------------------------------------------------------


def lanes_in_use(block_tables: jax.Array) -> jax.Array:
    """Which rows of a decode step's ``block_tables`` [B, width] are
    somebody's [B]: a padding lane's table is the trash block throughout,
    a request's starts with a block of its own."""
    return block_tables[:, 0] != TRASH_BLOCK


def token_slots(k_pages: jax.Array, block_tables: jax.Array,
                positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(block id, slot in it), each [B], of lane b's token at
    ``positions[b]``. A lane masked off is redirected by the caller:
    ``jnp.where(on, blocks, TRASH_BLOCK)``."""
    block = k_pages.shape[2]
    blocks = block_tables[jnp.arange(positions.shape[0]), positions // block]
    return blocks, positions % block


def sequence_slots(k_pages: jax.Array, block_tables: jax.Array,
                   lens: jax.Array, n_tokens: int,
                   start: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """(block id, slot in it), each [B, n_tokens], of token t of sequence b,
    which lies at position ``start[b] + t`` (``start`` None: at t). Tokens at
    and past ``lens[b]`` are padding and go to the trash block."""
    block = k_pages.shape[2]
    t = jnp.arange(n_tokens, dtype=jnp.int32)
    if start is None:
        blocks = block_tables[:, t // block]
    else:
        pos = start[:, None] + t[None, :]
        blocks = jnp.take_along_axis(block_tables, pos // block, axis=1)
    valid = t[None, :] < lens[:, None]
    blocks = jnp.where(valid, blocks, TRASH_BLOCK)
    if start is None:
        pos = t[None, :]
    return blocks, jnp.where(valid, pos % block, 0)


def write(k_pages: jax.Array, v_pages: jax.Array, k_new: jax.Array,
          v_new: jax.Array, blocks: jax.Array, slots: jax.Array
          ) -> tuple[jax.Array, jax.Array]:
    """Scatter new KV rows into their pages: ``k_new`` / ``v_new``
    [L, ..., Hkv, D] with ``blocks`` / ``slots`` [...] from
    :func:`token_slots` or :func:`sequence_slots` — every layer's rows in one
    scatter a pool, so donated pools are updated in place. A latent pool
    (``v_pages`` None) takes its rows as ``k_new`` [L, ..., latent_dim]. A
    token's heads go in as the page's rows hold them (several narrow heads
    side by side: the same values in the same order)."""
    blocks, slots = blocks.reshape(-1), slots.reshape(-1)
    if v_pages is None:
        return _write_latent_rows(k_pages, k_new, blocks, slots), None

    def rows(new, pool):
        return new.reshape(new.shape[0], -1, *pool.shape[-2:]).astype(
            pool.dtype)

    k_rows, v_rows = rows(k_new, k_pages), rows(v_new, v_pages)
    return (k_pages.at[:, blocks, slots].set(k_rows),
            v_pages.at[:, blocks, slots].set(v_rows))


def _stored(pool: jax.Array, rows: jax.Array) -> jax.Array:
    """Latent rows [..., latent_dim] as the pool stores them: its dtype,
    zero-padded to its row width."""
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, pool.shape[-1] - rows.shape[-1])]
    return jnp.pad(rows, pad).astype(pool.dtype)


def _write_latent_rows(pool: jax.Array, rows: jax.Array, blocks: jax.Array,
                       slots: jax.Array) -> jax.Array:
    """One row a (layer, token), rows [L, ..., latent_dim], into a latent
    pool, a page at a time: the token's page is read, the row set in it, and
    the page written back. A latent page is [block, row_width] with the
    tokens on the second-minor axis, where a TPU packs two bf16 rows into one
    word: scattered row by row, XLA re-lays-out the whole pool around the
    scatter (a copy of it in and out, a decode step; AOT, PR 32), while a
    scatter of whole pages updates the donated pool in place. Two tokens of
    one call never share a page, but for those sent to the trash block."""
    rows = _stored(pool, rows.reshape(rows.shape[0], -1, rows.shape[-1]))
    at = jnp.arange(pool.shape[2], dtype=slots.dtype)[None, :] == slots[:, None]
    pages = jnp.where(at[None, :, :, None], rows[:, :, None, :],
                      pool[:, blocks])                   # [L, n, block, width]
    return pool.at[:, blocks].set(pages)


def _write_latent_run(pool: jax.Array, rows: jax.Array,
                      block_tables: jax.Array, lens: jax.Array,
                      start: jax.Array | None) -> jax.Array:
    """A run of tokens a sequence, rows [L, B, S, latent_dim], into a latent
    pool from position ``start[b]`` on, whole pages at a time (see
    :func:`_write_latent_rows`). ``start`` is a multiple of the page (a cached
    prefix is whole pages, and so is a prefill window) and so is S; the last
    page's rows past ``lens[b]`` are written as computed — positions no
    sequence length reaches until a decode step sets them — and pages wholly
    past it go to the trash block."""
    L, B, S, _ = rows.shape
    block = pool.shape[2]
    first = jnp.arange(S // block, dtype=jnp.int32)[None, :]       # [1, S/blk]
    at = first if start is None else first + start[:, None] // block
    ids = jnp.take_along_axis(
        block_tables, jnp.minimum(at, block_tables.shape[1] - 1), axis=1)
    ids = jnp.where(first * block < lens[:, None], ids, TRASH_BLOCK)
    pages = _stored(pool, rows).reshape(L, B * (S // block), block, -1)
    return pool.at[:, ids.reshape(-1)].set(pages)


def write_sequences(k_pages: jax.Array, v_pages: jax.Array,
                    k_new: jax.Array, v_new: jax.Array,
                    block_tables: jax.Array, lens: jax.Array,
                    start: jax.Array | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """A run of tokens a sequence, ``k_new`` / ``v_new`` [L, B, S, Hkv, D]
    (a prefill's KV), written from position ``start[b]`` (None: 0) on;
    padding past ``lens[b]`` lands in the trash block. A cache with a state
    pool takes a first window's ``state.Fresh`` as ``k_new``: its K/V rows
    go to the pages and its slots' state starts afresh."""
    if isinstance(k_pages, state_pool.Cache):
        cache = state_pool.start(k_pages, k_new, *write_sequences(
            k_pages.k, k_pages.v, k_new.k, k_new.v, block_tables, lens,
            start))
        if cache.idx is not None:
            cache = dataclasses.replace(cache, idx=_write_latent_run(
                cache.idx, k_new.idx, block_tables, lens, start))
        if cache.win_v is not None:
            # The window layers' K and V, under the step's window tables: the
            # pages the request does not keep are the trash block there.
            win, win_v = write(
                cache.win, cache.win_v, k_new.win, k_new.win_v,
                *sequence_slots(cache.win, cache.wt, lens,
                                k_new.win.shape[2], start))
            cache = dataclasses.replace(cache, win=win, win_v=win_v)
        elif cache.win is not None:
            # (A latent window pool: the same, whole pages at a time.)
            cache = dataclasses.replace(cache, win=_write_latent_run(
                cache.win, k_new.win, cache.wt, lens, start))
        return cache, None
    if v_pages is None:
        return _write_latent_run(k_pages, k_new, block_tables, lens,
                                 start), None
    return write(k_pages, v_pages, k_new, v_new,
                 *sequence_slots(k_pages, block_tables, lens, k_new.shape[2],
                                 start))


# ---- reads --------------------------------------------------------------------


def use_kernel(head_dim: int, *, asked: bool | None, interpret: bool,
               platform: str, sharded: bool) -> bool:
    """Whether decode attention runs the Pallas kernel or the XLA gather.
    ``head_dim`` is a page's minor dim (``PageGeometry.shape[-1]``: a latent
    row is stored lane-aligned, and so is a K/V page whose rows hold several
    narrow heads side by side, ``ModelConfig.kv_heads_a_row``: their kernels
    always can; a K/V page of one narrow head a row cannot).
    Left open (``asked`` None), the kernel runs where it compiles and wins: a
    real TPU, single-device pages, a lane-aligned minor dim. Asked for by
    name and impossible is an error, not a quiet switch to the other path."""
    if asked is None:
        return platform == "tpu" and not sharded and head_dim % LANES == 0
    if asked and not interpret and head_dim % LANES != 0:
        raise ValueError(
            f"pallas_attention: head_dim {head_dim} is not lane-aligned "
            "(128) — Mosaic cannot slice the page DMA; leave the option "
            "unset to let the engine choose")
    return asked


def attention_for(geom: PageGeometry, *, kernel: bool, interpret: bool):
    """The decode attention of ``geom``'s kind of pool (a block's
    ``attention_fn``), bound to what :func:`use_kernel` decided."""
    return functools.partial(
        latent_decode_attention if geom.latent_dim else decode_attention,
        kernel=kernel, interpret=interpret)


def decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     layer: jax.Array, block_tables: jax.Array,
                     seq_lens: jax.Array, cur_k: jax.Array, cur_v: jax.Array,
                     *, kernel: bool = False,
                     interpret: bool = False) -> jax.Array:
    """One new token a lane, q [B, H, D], against ``layer``'s pages of the
    stacked pools; the token's own K/V (not in the pages yet) are ``cur_k`` /
    ``cur_v`` [B, Hkv, D]. ``seq_lens`` counts the current token. Returns
    [B, H, D]. ``kernel`` / ``interpret`` are bound by whoever decided
    (:func:`use_kernel`); the default is the XLA gather.

    Where a page row holds ``side`` narrow heads side by side (the pool's
    minor dim ``side`` x D), the kernel walks it as Hkv / side heads of
    ``side`` x D: a query row goes in zero outside the lanes of its own KV
    head, so its products with the row's other heads' keys add nothing, the
    scale stays 1 / sqrt(D), and the kernel takes each row's own D lanes of
    the ``side`` x D it accumulated and hands them back ``side`` query heads
    a row of whole lanes, ``[B, H / side, side x D]``: the same values in
    the same order as ``[B, H, D]``, which the reshape below and the
    caller's own to ``[B, H x D]`` undo with no operation (a ``[B, H, 64]``
    result would be re-laid out by a copy behind the kernel). The products
    multiply by ``side`` (the walk is bound by the page copies) and the
    bytes do not. The plain form reads the same pool as the ``[.., Hkv, D]``
    it is."""
    side = k_pages.shape[-1] // q.shape[-1]
    if kernel:
        if side > 1:
            B, H, D = q.shape
            # Query head h belongs to KV head h // (H / Hkv), which lies at
            # lanes (that % side) x D of its page row.
            own = (jnp.arange(H) // (H // cur_k.shape[1])) % side
            q = jnp.where(
                (own[:, None] == jnp.arange(side))[None, :, :, None],
                q[:, :, None, :], 0).reshape(B, H, side * D)
            cur_k, cur_v = (t.reshape(B, -1, side * D) for t in (cur_k, cur_v))
        out = paged_decode_attention_pallas(
            q, k_pages, v_pages, layer, block_tables, seq_lens, cur_k, cur_v,
            side=side, interpret=interpret)
        return (out if side == 1 else
                out.reshape(out.shape[0], -1, out.shape[-1] // side))
    if side > 1:
        k_pages, v_pages = (p.reshape(*p.shape[:3], -1, q.shape[-1])
                            for p in (k_pages, v_pages))
    return paged_decode_attention(q, k_pages, v_pages, layer, block_tables,
                                  seq_lens, cur_k=cur_k, cur_v=cur_v)


def latent_decode_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                            block_tables: jax.Array, seq_lens: jax.Array,
                            cur_row: jax.Array, *, value_dim: int,
                            scale: float, kernel: bool = False,
                            interpret: bool = False,
                            keep: jax.Array | None = None,
                            cur_keep: jax.Array | None = None) -> jax.Array:
    """:func:`decode_attention` for a latent pool, in the absorbed form: q
    [B, H, latent_dim] (carried into the latent space, then its rotated
    part) against ``layer``'s rows, each key and value at once, and the
    token's own row ``cur_row`` [B, latent_dim]. Returns [B, H, value_dim]:
    the probabilities over the rows' leading ``value_dim`` columns (the
    latent, which the caller carries out through the value projection).
    With ``keep`` [B, table width x block] and ``cur_keep`` [B] (a block that
    selects rows) the softmax is over the selected rows alone, the current
    token among them or not."""
    if keep is not None:
        op = (functools.partial(sparse_latent_paged_decode_attention_pallas,
                                interpret=interpret)
              if kernel else sparse_latent_paged_decode_attention)
        return op(q, pool, layer, block_tables, seq_lens, cur_row, keep,
                  cur_keep, value_dim=value_dim, scale=scale)
    op = (functools.partial(latent_paged_decode_attention_pallas,
                            interpret=interpret)
          if kernel else latent_paged_decode_attention)
    return op(q, pool, layer, block_tables, seq_lens, cur_row,
              value_dim=value_dim, scale=scale)


def window_decode_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                            block_tables: jax.Array, seq_lens: jax.Array,
                            cur_row: jax.Array, *, value_dim: int,
                            scale: float, window: int,
                            impl: str = "xla") -> jax.Array:
    """:func:`latent_decode_attention` for a window pool: the query sees its
    own row and the ``window - 1`` cached before it, through the lane's
    window table. ``impl`` is the form the program traces with
    (``ModelConfig.swa_impl``): "kernel", "kernel_interpret" or "xla"."""
    if impl.startswith("kernel"):
        return swa_latent_decode_attention_pallas(
            q, pool, layer, block_tables, seq_lens, cur_row,
            value_dim=value_dim, scale=scale, window=window,
            interpret=impl == "kernel_interpret")
    return swa_latent_decode_attention(
        q, pool, layer, block_tables, seq_lens, cur_row,
        value_dim=value_dim, scale=scale, window=window)


def window_kv_decode_attention(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, layer: jax.Array,
                               block_tables: jax.Array, seq_lens: jax.Array,
                               cur_k: jax.Array, cur_v: jax.Array, *,
                               window: int, impl: str = "xla") -> jax.Array:
    """:func:`decode_attention` for a window pool's K/V pair: the query sees
    its own K/V and the ``window - 1`` rows cached before it, through the
    lane's window table. ``impl`` as :func:`window_decode_attention`'s."""
    if impl.startswith("kernel"):
        return swa_paged_decode_attention_kernel(
            q, k_pages, v_pages, layer, block_tables, seq_lens, cur_k, cur_v,
            window=window, interpret=impl == "kernel_interpret")
    return swa_paged_decode_attention(q, k_pages, v_pages, layer,
                                      block_tables, seq_lens, cur_k, cur_v,
                                      window=window)


def window_prefix_pages(table_row: jax.Array, prefix_len: jax.Array,
                        block: int, window: int
                        ) -> tuple[jax.Array, jax.Array]:
    """The pages of a window pool that a prefill window starting at
    ``prefix_len`` [1] (a multiple of the page) can still see, out of the
    sequence's window table [1, W]: (their ids [1, P], the positions of
    their rows [1, P * block]), the P pages that end where the window starts
    (from page 0 where fewer lie before it: the pages at and past
    ``prefix_len`` are read as the trash block, and the caller masks their
    positions). :func:`read_latent_prefix` reads them."""
    n = -(-(window - 1) // block)
    first = jnp.maximum(prefix_len // block - n, 0)               # [1]
    at = first[:, None] + jnp.arange(n, dtype=first.dtype)[None, :]
    ids = jnp.take_along_axis(
        table_row, jnp.minimum(at, table_row.shape[1] - 1), axis=1)
    ids = jnp.where(at * block < prefix_len[:, None], ids, TRASH_BLOCK)
    pos = (first[:, None] * block
           + jnp.arange(n * block, dtype=first.dtype)[None, :])
    return ids, pos


def read_latent_prefix(pool: jax.Array, layer: jax.Array,
                       table_row: jax.Array, latent_dim: int) -> jax.Array:
    """A sequence's cached latent rows out of ``layer`` of the stacked pool:
    the blocks of ``table_row`` [1, W] in order, as [1, W * block,
    latent_dim], the stored padding dropped. One gather at (layer, page): no
    layer's pool is sliced out first."""
    rows = pool[layer, table_row]                    # [1, W, block, width]
    return rows.reshape(1, -1, rows.shape[-1])[..., :latent_dim]


def read_rows(pool: jax.Array, layer: jax.Array,
              block_tables: jax.Array) -> jax.Array:
    """Every lane's rows of ``layer`` of a stacked latent-kind pool (the
    indexer's keys at decode), ``block_tables`` [B, W] in order: [B, W *
    block, width], padding entries the trash block's. One gather at (layer,
    page)."""
    rows = pool[layer, block_tables]                  # [B, W, block, width]
    return rows.reshape(rows.shape[0], -1, rows.shape[-1])


def read_prefix(k_layer: jax.Array, v_layer: jax.Array,
                table_row: jax.Array, layer: int | None = None,
                heads: tuple[int, int] | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """A sequence's cached KV out of ONE layer's pool [N, block, Hkv, D], as
    a scan over the stacked pools hands it to its body: the blocks of
    ``table_row`` [1, W] in order, as [1, W * block, Hkv, D] each. With
    ``layer`` the pools are the stacked ones and that layer of them is read
    (a module that walks its layers in Python: models/hybrid.py). ``heads``:
    the model's own (Hkv, D) where a page row holds several heads side by
    side (None: the pool's)."""
    def gather(pool):
        rows = pool[table_row] if layer is None else pool[layer, table_row]
        return rows.reshape(1, -1, *(heads or pool.shape[-2:]))

    return gather(k_layer), gather(v_layer)


def prefill_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                      pools: tuple[jax.Array, jax.Array],
                      near_pools: tuple[jax.Array, jax.Array],
                      is_near: jax.Array, layer: jax.Array,
                      table_row: jax.Array, near_table_row: jax.Array,
                      near_first_pos: jax.Array, prefix_len: jax.Array,
                      suffix_len: jax.Array, *, window: int,
                      impl: str = "xla") -> jax.Array:
    """A continuation window's queries q [1, S, H, D] (at ``prefix_len`` [1]
    on, ``suffix_len`` [1] of them real) against the window's own K/V [1, S,
    Hkv, D], not in the pages yet, and the rows cached before it, for a
    layer of either kind of a model with both (``is_near``, traced: a scan
    decides it; ``layer`` counts among its kind): a layer that sees
    everything reads ``pools`` (the stacked K and V) by ``table_row`` [1, W],
    positions 0 on; one that sees a ``window`` reads ``near_pools`` by
    ``near_table_row`` (:func:`window_prefix_pages`), positions
    ``near_first_pos`` [1] on; in either the rows below ``prefix_len``.
    Returns [1, S, H, D]. ``impl`` as :func:`window_decode_attention`'s: the
    kernel is ONE call for both kinds that walks the pages the prompt holds
    itself, the pools left as they lie, and reads nothing of a table past
    them; the plain form gathers a table's rows whole (:func:`read_prefix`)
    and bands them a block of queries at a time, the scores through memory,
    a ``cond`` branch a kind (and the compiler re-lays out each V pool once
    a program for the probabilities' product: PERF.md section 7, PR 48)."""
    if impl.startswith("kernel"):
        return kv_window_prefill_attention(
            q, k_new, v_new, *pools, *near_pools, is_near, layer, table_row,
            near_table_row, near_first_pos, prefix_len, suffix_len,
            window=window, interpret=impl == "kernel_interpret")
    own = jnp.arange(q.shape[1], dtype=jnp.int32)[None, :]
    positions = prefix_len[:, None] + own

    def banded(pools, table_row, first_pos, window):
        k_prior, v_prior = read_prefix(*pools, table_row, layer=layer)
        pos = first_pos[:, None] + jnp.arange(k_prior.shape[1],
                                              dtype=jnp.int32)[None, :]
        return banded_attention(
            q, jnp.concatenate([k_prior.astype(k_new.dtype), k_new], axis=1),
            jnp.concatenate([v_prior.astype(v_new.dtype), v_new], axis=1),
            q_positions=positions,
            kv_positions=jnp.concatenate([pos, positions], axis=1),
            kv_valid=jnp.concatenate([pos < prefix_len[:, None],
                                      own < suffix_len[:, None]], axis=1),
            window=window)

    return jax.lax.cond(
        is_near,
        lambda: banded(near_pools, near_table_row, near_first_pos, window),
        lambda: banded(pools, table_row, jnp.zeros_like(prefix_len), None))


# ---- export and import, block-wise --------------------------------------------
# (of the K/V pair: a latent pool is not handed off yet, and an engine that
# holds one refuses the roles and requests that would; ROADMAP R8)


def gather_blocks(k_pages, v_pages, ids):
    """Blocks ``ids`` [n] of every layer, as a pair shaped like a pool of n
    blocks: a copy, so the blocks may be freed at once."""
    return k_pages[:, ids], v_pages[:, ids]


def scatter_blocks(k_pages, v_pages, ids, k_new, v_new):
    """The inverse: a gathered pair written at blocks ``ids`` (padding
    entries point at the trash block)."""
    return k_pages.at[:, ids].set(k_new), v_pages.at[:, ids].set(v_new)


def block_range(k, v, lo: int, hi: int):
    """Blocks [lo, hi) of a gathered pair."""
    return k[:, lo:hi], v[:, lo:hi]


def pad_blocks(k: np.ndarray, v: np.ndarray,
               n_blocks: int) -> tuple[np.ndarray, np.ndarray]:
    """A gathered pair on the host, zero-padded to ``n_blocks`` blocks so
    that one compiled scatter serves every import."""
    def pad(a):
        out = np.zeros((a.shape[0], n_blocks, *a.shape[2:]), a.dtype)
        out[:, :a.shape[1]] = a
        return out

    return pad(k), pad(v)
