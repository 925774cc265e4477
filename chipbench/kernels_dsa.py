"""What the two kernels of learned sparse attention (DeepSeek-V3.2's) need at
decode: the yardstick's operation and byte counts for `dsa_indexer_roofline`
and `dsa_attention_roofline`, beside kernels.py (whose peaks and
`roofline_seconds` they are read with).

As there, the counts are what the ALGORITHM needs from its shapes, whatever
implements it: the indexer reads each context token's key once and scores it
against every light head; attention reads the SELECTED rows once (at most
`index_topk` a query) and uses each as key and as value. A program that
gathers keys before it scores them, or reads whole pages and masks the rows
that were not chosen, reads more than is counted here, and its share is lower
for it, as it should be.
"""

from __future__ import annotations


def indexer_decode(context_tokens: float, lanes: float, index_heads: int,
                   index_dim: int, itemsize: int = 2) -> dict[str, float]:
    """One call of the indexer (one layer, one step) over `lanes` sequences
    whose contexts sum to `context_tokens`.

    FLOPs: every light head's query against a key, 2 * heads * dim per
    context token. Bytes: every context token's key once (dim values), plus
    per lane the heads' queries and an f32 weight a head."""
    flops = 2.0 * index_heads * index_dim * context_tokens
    key_bytes = index_dim * itemsize * context_tokens
    lane_bytes = lanes * index_heads * (index_dim * itemsize + 4)
    return {"flops": flops, "bytes": key_bytes + lane_bytes}


def selected_attention_decode(context_tokens: float, lanes: float,
                              index_topk: int, n_heads: int, latent_dim: int,
                              value_dim: int, itemsize: int = 2
                              ) -> dict[str, float]:
    """One call of decode attention over the selected rows (one layer, one
    step), absorbed form: a lane attends to min(context, index_topk) rows.
    The trace gives the lanes' contexts as a sum, so the rows are
    min(context_tokens, lanes * index_topk): exact where every lane is on one
    side of index_topk, and an overcount of the rows (a lower bound of no
    kind) only where long and short lanes mix -- the caller hands the mean
    context and says which.

    FLOPs: 2 * heads * (latent_dim + value_dim) per selected row. Bytes:
    every selected row once (latent_dim values), plus per lane the query
    (heads * latent_dim), the new row, and the output (heads * value_dim)."""
    rows = min(context_tokens, lanes * index_topk)
    flops = 2.0 * n_heads * (latent_dim + value_dim) * rows
    row_bytes = latent_dim * itemsize * rows
    lane_bytes = lanes * itemsize * (n_heads * (latent_dim + value_dim)
                                     + latent_dim)
    return {"flops": flops, "bytes": row_bytes + lane_bytes}
