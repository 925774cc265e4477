"""What decode attention over a WINDOW of K/V pages needs: the yardstick's
operation and byte counts for `swa_kv_decode_roofline`, beside kernels.py
(whose peaks and `roofline_seconds` they are read with) and kernels_swa.py
(the latent cache's form of the same).

As there, the counts are what the ALGORITHM needs from its shapes, whatever
implements it: a query attends to its own K/V and to the `window - 1` rows
cached before it, and reads each of those rows' K and V once for all the
query heads of a KV head. A program that reads whole pages and masks the rows
before the window, walks a table wider than the lane's window, or repeats a
KV head's rows for its query heads reads more than is counted here, and its
share is lower for it, as it should be.
"""

from __future__ import annotations

import kernels


def window_kv_attention_decode(context_tokens: float, lanes: float,
                               window: int, n_heads: int, n_kv_heads: int,
                               head_dim: int, itemsize: int = 2
                               ) -> dict[str, float]:
    """One call of decode attention of a window layer (one layer, one step):
    a lane attends to min(context, window) rows. The trace gives the lanes'
    contexts as a sum, so the rows are min(context_tokens, lanes * window):
    exact where every lane is on one side of the window (the cell's prompts
    start at the window's length), an overcount of the rows only where long
    and short lanes mix.

    The counts a row and a lane are the full layers' kernel's
    (`kernels.paged_attention_decode`): q.K^T and p.V, 2 * heads * head_dim
    FLOPs each a row attended to; every such row's K and V once (kv_heads *
    head_dim each), plus per lane the query, the new K and V rows, and the
    output."""
    return kernels.paged_attention_decode(
        min(context_tokens, lanes * window), lanes, n_heads, n_kv_heads,
        head_dim, itemsize)
