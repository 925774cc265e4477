"""KV-cache block allocator.

Physical block 0 is reserved as the trash block: padding lanes and inactive
decode slots scatter their writes there (models/llama.py relies on this), so
the hot-path scatters stay static-shaped with no masking branches.
"""

from __future__ import annotations

import numpy as np


class OutOfBlocks(Exception):
    pass


class BlockAllocator:
    TRASH = 0

    def __init__(self, n_blocks: int, block_size: int):
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free: list[int] = list(range(n_blocks - 1, 0, -1))  # pop() yields 1,2,…

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_fraction(self) -> float:
        usable = self.n_blocks - 1
        return (usable - len(self._free)) / usable if usable else 0.0

    def blocks_for_tokens(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def alloc(self, n: int) -> list[int]:
        """n blocks, in ascending order. A table's physical order means
        nothing to its owner, and a request takes its whole table at once:
        ascending, the neighbours the pool still has sit side by side in the
        table, where the latent kernels fetch them as one copy
        (ops/pallas_latent_attention.stage_fetch: 87% of a table's groups of
        8 under the long-context cell's churn, 54% as taken;
        tests/test_prefix_caching.py counts it)."""
        return sorted(self._take(n))

    def _take(self, n: int) -> list[int]:
        if n > len(self._free):
            raise OutOfBlocks(f"need {n} blocks, have {len(self._free)}")
        return [self._free.pop() for _ in range(n)]

    def free(self, blocks: list[int]) -> None:
        for b in blocks:
            if b == self.TRASH:
                raise ValueError("attempt to free trash block 0")
            self._free.append(b)


class PrefixCachingAllocator(BlockAllocator):
    """Block allocator with automatic prefix caching (the engine-side analogue
    of vLLM's APC, which the reference's prefix scorers assume exists on every
    pod — SURVEY §2.5's CacheBlockSize/CacheNumBlocks telemetry).

    Complete prompt blocks are content-addressed by their chained hash
    (utils/hashing.py). On release, hash-committed blocks with no remaining
    references park in a reusable LRU instead of the free list; a later
    request whose prompt shares the prefix re-acquires them (refcount++) and
    skips recomputing that KV. New allocations evict from the LRU only when
    the free list runs dry.
    """

    def __init__(self, n_blocks: int, block_size: int):
        super().__init__(n_blocks, block_size)
        from collections import OrderedDict

        self._ref: dict[int, int] = {}
        self._hash_of: dict[int, int] = {}        # block id -> content hash
        self._by_hash: dict[int, int] = {}        # content hash -> block id
        self._cached_lru: "OrderedDict[int, None]" = OrderedDict()  # bid -> None

    # ---- capacity ------------------------------------------------------

    @property
    def reusable_blocks(self) -> int:
        return len(self._free) + len(self._cached_lru)

    @property
    def used_fraction(self) -> float:
        usable = self.n_blocks - 1
        active = sum(1 for c in self._ref.values() if c > 0)
        return active / usable if usable else 0.0

    @property
    def cached_block_count(self) -> int:
        return len(self._cached_lru)

    def cached_hashes(self) -> list[int]:
        """All content-addressed block hashes (active + parked reusable)."""
        return list(self._by_hash.keys())

    # ---- prefix matching ----------------------------------------------

    def match_prefix(self, hashes: list[int]) -> list[int]:
        """Longest consecutive run of cached blocks for this hash chain
        (no refcount change; pair with acquire_cached)."""
        out = []
        for h in hashes:
            bid = self._by_hash.get(h)
            if bid is None:
                break
            out.append(bid)
        return out

    def acquire_cached(self, bids: list[int]) -> None:
        for bid in bids:
            self._ref[bid] = self._ref.get(bid, 0) + 1
            self._cached_lru.pop(bid, None)

    # ---- alloc / release ----------------------------------------------

    def _take(self, n: int) -> list[int]:
        """Take n blocks, evicting parked cached blocks LRU-first when the
        free list is short. Returns block ids as taken (``alloc`` sorts
        them); evicted content hashes are collected in
        self.last_evicted_hashes, in eviction order, for cache-event
        publication."""
        self.last_evicted_hashes: list[int] = []
        if n > self.reusable_blocks:
            raise OutOfBlocks(f"need {n} blocks, have {self.reusable_blocks}")
        out = []
        for _ in range(n):
            if self._free:
                bid = self._free.pop()
            else:
                bid, _ = self._cached_lru.popitem(last=False)  # LRU eviction
                h = self._hash_of.pop(bid, None)
                if h is not None:
                    self._by_hash.pop(h, None)
                    self.last_evicted_hashes.append(h)
            self._ref[bid] = 1
            out.append(bid)
        return out

    def commit_hashes(self, bids: list[int], hashes: list[int]) -> None:
        """Content-address freshly prefilled complete blocks."""
        for bid, h in zip(bids, hashes):
            prev = self._by_hash.get(h)
            if prev is not None and prev != bid:
                continue  # already cached elsewhere; keep the existing mapping
            self._hash_of[bid] = h
            self._by_hash[h] = bid

    def release(self, bids: list[int]) -> None:
        """Drop one reference; unreferenced blocks park (if hash-committed)
        or free."""
        for bid in bids:
            if bid == self.TRASH:
                raise ValueError("attempt to release trash block 0")
            c = self._ref.get(bid, 0) - 1
            if c > 0:
                self._ref[bid] = c
                continue
            self._ref.pop(bid, None)
            if bid in self._hash_of:
                self._cached_lru[bid] = None
                self._cached_lru.move_to_end(bid)
            else:
                self._free.append(bid)

    # Legacy API parity: free == release (used by abort paths).
    def free(self, blocks: list[int]) -> None:
        self.release(blocks)


class Table(list):
    """A request's block table where the cache has two kinds of layer
    (:class:`WindowedAllocator`): the list is the table of the layers that
    keep the whole context, as every other request's is, and beside it ride
    the pages the request holds of the window layers' pool, whole stretches
    of them: ``window[i]`` the page of logical page ``first + i``, ``first``
    a multiple of the stretch (0 where it holds none: the trash block)."""

    def __init__(self, blocks=()):
        super().__init__(blocks)
        self.first: int = 0
        self.window: list[int] = []


class WindowedAllocator(BlockAllocator):
    """One owner of both kinds of cache layer (kvcache/pages.py): the blocks
    of the layers that keep the whole context are this allocator's own, a
    request's whole table at admission as ever; the pages of the layers that
    keep a WINDOW of it come from a second pool, a step at a time
    (:meth:`slide`), and go back once every row of them lies more than
    ``window - 1`` behind the request's position.

    The window pool is given and taken back in aligned STRETCHES of ``run``
    pages: logical pages ``[run * k, run * k + run)`` of a request are
    physical pages ``[1 + run * j, 1 + run * j + run)``, taken when the
    request first writes into them, given back when every row of all of them
    is out of reach. The decode kernels fetch a table in aligned groups of
    ``run`` entries, ONE copy where a group names adjacent ascending blocks
    (ops/pallas_latent_attention.stage_fetch), and cut a lane's window table
    at a multiple of ``run`` (ops/attention.window_table): a stretch is such
    a group whatever its neighbours are, so the free list of stretches stays
    LIFO. (A page at a time off a LIFO list that every lane shares, the
    window tables held next to no run: 22% of a decoding lane's groups after
    250 chunks of the long-context cell's traffic, none after 2,000.)

    Admission reserves by kind: a table is handed out only while a
    reservation of ``lane_stretches`` stretches is left for it (``lanes`` of
    them: the pool's size follows the engine's lanes, never a request's
    length), so :meth:`slide` cannot run dry; with none left the allocator
    reports no free block and the head of the queue waits."""

    def __init__(self, n_blocks: int, block_size: int, *, window_blocks: int,
                 window: int, lanes: int, lane_stretches: int, run: int):
        super().__init__(n_blocks, block_size)
        self.window, self.run = window, run
        self.lanes, self.lane_stretches = lanes, lane_stretches
        # Stretch j (from 1: 0 stays the trash block's) is pages
        # [1 + run * (j - 1), 1 + run * j) of the window pool.
        self.stretches = BlockAllocator(1 + (window_blocks - 1) // run,
                                        block_size)
        self.tables = 0          # live tables: each holds a reservation

    @property
    def free_blocks(self) -> int:
        return len(self._free) if self.tables < self.lanes else 0

    @property
    def window_used_fraction(self) -> float:
        return self.stretches.used_fraction

    def free_window_pages(self) -> list[int]:
        """The window pool's pages that no request holds."""
        return [page for j in self.stretches._free
                for page in range(1 + self.run * (j - 1), 1 + self.run * j)]

    def alloc(self, n: int) -> Table:
        if self.tables >= self.lanes:
            raise OutOfBlocks(
                f"every reservation of the window pool is taken "
                f"({self.lanes} tables of {self.lane_stretches} stretches of "
                f"{self.run} pages)")
        self.tables += 1
        return Table(super().alloc(n))

    def free(self, blocks: list[int]) -> None:
        super().free(blocks)
        self._give_back(blocks.window)
        blocks.window = []
        self.tables -= 1

    def slide(self, table: Table, start: int, end: int, row,
              ahead: bool = False) -> None:
        """``table``'s window pages for a step that writes positions
        [start, end) and whose first query sits at ``start``: the stretches
        all of whose rows lie before ``start - (window - 1)`` go back to the
        pool, the stretches up to ``end - 1``'s that hold a page the
        request's next step can still see are taken (a long prefill window's
        early stretches never are: their rows go to the trash block), and
        ``row`` (a table row by logical page, zeros) is filled with what the
        request holds. With ``ahead`` (a step that is one program run once: a
        prefill window) what only THIS step reads, the stretches before
        ``end - (window - 1)``'s, goes back as soon as the row is filled:
        the device runs its programs in order, so whoever takes such a page
        writes it after this step has read it. A decode chunk runs its steps
        in one program and keeps them."""
        block, reach, run = self.block_size, self.window - 1, self.run
        self._drop(table, max(start - reach, 0) // block)
        keep = max(start, end - reach, 0) // block
        # (A chunk that overshoots the table's width writes nobody's rows.)
        last = min((end - 1) // block, len(row) - 1)
        for at in range(table.first + len(table.window), last + 1, run):
            if at + run > keep:
                page = 1 + run * (self.stretches.alloc(1)[0] - 1)
                table.window += range(page, page + run)
            else:
                table.window += [0] * run
        held = table.window[:max(len(row) - table.first, 0)]
        row[table.first:table.first + len(held)] = held
        if ahead:
            self._drop(table, max(end - reach, 0) // block)

    def _drop(self, table: Table, upto: int) -> None:
        """Give back ``table``'s stretches that lie whole before logical
        page ``upto``."""
        upto -= upto % self.run
        n = min(max(upto - table.first, 0), len(table.window))
        self._give_back(table.window[:n])
        del table.window[:n]
        table.first += n
        if not table.window:      # nothing held: the next stretch is upto's
            table.first = max(table.first, upto)

    def _give_back(self, pages: list[int]) -> None:
        """The stretches whose pages ``pages`` lists, whole and in order."""
        self.stretches.free([1 + (page - 1) // self.run
                             for page in pages[::self.run] if page])


def allocator_for(geom, prefix_caching: bool) -> BlockAllocator:
    """The owner of an engine's cache blocks, from what its cache keeps
    (``geom``: kvcache/pages.PageGeometry). A cached block prefix is pages
    with no recurrent state and no window rows to go with them: a model that
    keeps either keeps no prefix cache."""
    if geom.window is not None:
        w = geom.window
        return WindowedAllocator(
            geom.n_blocks, geom.block, window_blocks=w.n_blocks,
            window=w.window, lanes=w.lanes, lane_stretches=w.lane_stretches,
            run=geom.run_pages)
    if prefix_caching and not geom.state:
        return PrefixCachingAllocator(geom.n_blocks, geom.block)
    return BlockAllocator(geom.n_blocks, geom.block)


def table_groups(blocks: list[int], group: int) -> tuple[int, int]:
    """(runs, splits) of a request's block table, as the decode kernels walk
    it at full length: aligned groups of ``group`` entries, a run where they
    name adjacent blocks in ascending order (one copy), a split otherwise
    (a copy a page; a short last group is one)."""
    runs = sum(
        blocks[i:i + group] == list(range(blocks[i], blocks[i] + group))
        for i in range(0, len(blocks) - group + 1, group))
    return runs, -(-len(blocks) // group) - runs


def window_table_groups(row, position: int, block: int, window: int,
                        group: int) -> tuple[int, int]:
    """(runs, splits) of a decoding lane's window table ``row`` (by logical
    page) as the window layers' decode kernels cut and walk it for the query
    at ``position`` (ops/attention.window_table with ``align`` = ``group``,
    ops/pallas_latent_attention.table_runs): from the aligned group of the
    window's first page, a run where a group lies whole inside the lane's
    cached pages and names adjacent ascending blocks, a split otherwise (the
    short last group among them)."""
    first = max(position + 1 - window, 0) // block
    first -= first % group
    n_pages = -(-position // block) - first
    whole = np.asarray(row[first:first + n_pages // group * group]
                       ).reshape(-1, group)
    runs = int((whole == whole[:, :1] + np.arange(group)).all(axis=1).sum())
    return runs, -(-n_pages // group) - runs
