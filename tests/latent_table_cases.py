"""Block tables for the three paged decode walks of the latent family
(ops/pallas_latent_attention.stage_fetch fetches a stage in groups of R table
entries, one copy where a group names adjacent blocks in ascending order and
lies inside the lane's cached pages): every way a table can hold runs or
none. tests/test_mla.py and tests/test_dsa.py run each kernel against its
plain form over all of them, and tests/test_pallas_paged_attention.py and
tests/test_smallthinker.py the K/V family's two walks, which fetch a stage
the same way (:func:`kv_pools_under`).

Three lanes a case, pages of 16 tokens, a table of 48 entries, stages of 16
pages (the tests shrink the VMEM budget to that), so R = 8: two groups a
stage, three stages a table.
"""

import numpy as np

BLOCK = 16
STAGE = 16          # pages a stage
WIDTH = 48          # table entries a lane
N_BLOCKS = 400      # the pool's pages, the trash page 0 among them
# What shrinks a stage to STAGE pages of 128 f32 values a row
# (pallas_latent_attention.pages_per_stage).
STAGE_VMEM_BYTES = STAGE * BLOCK * 128 * (2 * 4 + 4)

FULL = WIDTH * BLOCK + 1    # a lane whose cached rows fill its table


def _lanes(case: str, group: int):
    """[(table entries, seq_len incl. the current token)] of three lanes."""
    rng = np.random.default_rng(7)

    def up(first, n):
        return list(range(first, first + n))

    if case == "all_runs":
        return [(up(1, WIDTH), FULL), (up(100, WIDTH), FULL),
                (up(200, WIDTH), 3 * group * BLOCK + 1)]
    if case == "no_runs":
        ids = rng.permutation(np.arange(1, N_BLOCKS)).tolist()
        return [(ids[:WIDTH], FULL), (ids[WIDTH:2 * WIDTH], FULL - 5),
                (ids[2 * WIDTH:3 * WIDTH], 300)]
    if case == "descending_runs":
        return [(up(1, WIDTH)[::-1], FULL), (up(100, WIDTH)[::-1], 400),
                # descending by groups that each ascend: runs again
                (sum((up(300 - 10 * g, group) for g in range(WIDTH // group)),
                     []), FULL)]
    if case == "run_crosses_the_last_live_page":
        # Adjacent blocks to the table's end, the cached rows ending inside
        # a group: that group falls back to a copy a page, and stops at the
        # lane's last page.
        return [(up(1, WIDTH), (2 * group + 3) * BLOCK - 6),
                (up(100, WIDTH), (group - 1) * BLOCK - 2),
                (up(200, WIDTH), (STAGE + 1) * BLOCK + 1)]
    if case == "run_crosses_a_stages_edge":
        # Adjacent blocks from the middle of one group through the stage's
        # edge (entry 16) into the middle of another.
        far = rng.permutation(np.arange(300, N_BLOCKS)).tolist()
        head = group // 2
        return [(far[:head] + up(20, 3 * group)
                 + far[head:WIDTH - 3 * group], FULL),
                (far[50:50 + STAGE - 2] + up(100, WIDTH - STAGE + 2), FULL),
                (up(200, STAGE - 1) + [7] + up(215, WIDTH - STAGE), 700)]
    if case == "empty_and_one_page_lanes":
        return [(up(1, WIDTH), 0), (up(100, WIDTH), 1),
                (up(200, WIDTH), BLOCK + 1)]
    if case == "trash_padding_behind_a_short_lane":
        return [(up(5, 2), 20),
                (up(100, 2 * group + 4), (2 * group + 4) * BLOCK), ([], 1)]
    if case == "borrowed_head_then_a_run":
        # A prefix hit's blocks lead the table, in whatever order their first
        # owner took them; the request's own follow, ascending.
        return [([90, 91, 92, 40, 41] + up(120, WIDTH - 5), FULL),
                (up(60, group) + [33, 35, 34] + up(250, WIDTH - group - 3),
                 FULL - 100),
                (up(10, 2 * group) + up(180, WIDTH - 2 * group), FULL)]
    raise ValueError(case)


CASES = ["all_runs", "no_runs", "descending_runs",
         "run_crosses_the_last_live_page", "run_crosses_a_stages_edge",
         "empty_and_one_page_lanes", "trash_padding_behind_a_short_lane",
         "borrowed_head_then_a_run"]

# Groups the kernel must fetch as one copy, by lane (counted by hand from
# the tables above at R = 8).
RUNS = {
    "all_runs": [6, 6, 3],
    "no_runs": [0, 0, 0],
    "descending_runs": [0, 0, 6],
    "run_crosses_the_last_live_page": [2, 0, 2],
    "run_crosses_a_stages_edge": [2, 4, 4],
    "empty_and_one_page_lanes": [0, 0, 0],
    "trash_padding_behind_a_short_lane": [0, 2, 0],
    "borrowed_head_then_a_run": [5, 4, 6],
}


def tables(case: str, group: int = 8):
    """(block tables [3, WIDTH] int32, seq_lens [3] int32) of ``case``, a
    short table padded with the trash page."""
    lanes = _lanes(case, group)
    table = np.zeros((len(lanes), WIDTH), np.int32)
    for row, (entries, _) in zip(table, lanes):
        assert len(entries) <= WIDTH and max(entries, default=0) < N_BLOCKS
        row[:len(entries)] = entries
    return table, np.asarray([n for _, n in lanes], np.int32)


def runs_by_hand(table, seq_lens, group: int = 8):
    """ops/pallas_latent_attention.table_runs, a loop at a time."""
    out = np.zeros((table.shape[0], WIDTH // group), np.int32)
    for lane, row in enumerate(table):
        n_pages = -(-(int(seq_lens[lane]) - 1) // BLOCK)
        for g in range(WIDTH // group):
            entries = row[g * group:(g + 1) * group]
            out[lane, g] = ((g + 1) * group <= n_pages and all(
                entries[i] == entries[0] + i for i in range(group)))
    return out


def pool_under(table, seq_lens, width: int, stored: int, seed: int):
    """A two-layer pool [2, N_BLOCKS, BLOCK, stored] f32 whose second layer
    holds random rows (``width`` values, zeros to ``stored``) at the pages
    each lane owns up to its length, and large values everywhere else: the
    rest of a last page, pages nobody owns, the trash page. Whatever a walk
    reads that it should not, or fails to mask, shows."""
    pool = np.full((2, N_BLOCKS, BLOCK, stored), 1e4, np.float32)
    for lane, n in enumerate(seq_lens):
        cached = max(int(n) - 1, 0)
        rows = np.random.default_rng([seed, lane]).standard_normal(
            (WIDTH * BLOCK, width), np.float32)
        for i, blk in enumerate(table[lane, :-(-cached // BLOCK)]):
            pool[1, blk, :, :width] = rows[i * BLOCK:(i + 1) * BLOCK]
            pool[1, blk, :, width:] = 0
        if cached % BLOCK:
            pool[1, table[lane, cached // BLOCK], cached % BLOCK:] = 1e4
    return pool


def kv_pools_under(table, seq_lens, n_kv: int, head_dim: int, seed: int):
    """A K and a V pool [2, N_BLOCKS, BLOCK, n_kv, head_dim] f32 under
    ``table`` as :func:`pool_under` lays one out: random rows at the pages
    each lane owns up to its length in the second layer, large values
    elsewhere."""
    return [pool_under(table, seq_lens, n_kv * head_dim, n_kv * head_dim,
                       seed=seed + i).reshape(2, N_BLOCKS, BLOCK, n_kv,
                                              head_dim) for i in range(2)]


def moved(table, pools, seed: int):
    """The same rows under the same logical tables at other physical pages,
    shuffled so that no group is a run: (table, pools)."""
    to = np.concatenate([[0], 1 + np.random.default_rng(seed).permutation(
        N_BLOCKS - 1)])
    back = np.argsort(to)
    return to[table].astype(np.int32), [pool[:, back] for pool in pools]


def shrink_kv_stage(monkeypatch, paged, n_kv: int, head_dim: int,
                    width: int = WIDTH) -> int:
    """Hold ops/pallas_paged_attention (``paged``) to stages of STAGE pages
    of f32 at these heads; P for a walk of ``width`` table entries."""
    monkeypatch.setattr(
        paged, "STAGE_VMEM_BYTES",
        STAGE * paged.stage_vmem_bytes(1, BLOCK, n_kv, head_dim, 4))
    return paged.pages_per_stage(BLOCK, n_kv, head_dim, 4, width)
