"""What decode attention over a WINDOW of a latent cache needs: the
yardstick's operation and byte counts for `swa_decode_roofline`, beside
kernels.py (whose peaks and `roofline_seconds` they are read with).

As there, the counts are what the ALGORITHM needs from its shapes, whatever
implements it: a query attends to its own row and to the `window - 1` cached
before it, reads each of those rows once and uses it as key and as value
(the absorbed form: a row is `latent_dim` values for every head). A program
that reads whole pages and masks the rows before the window, or reads rows
stored wider than they are, reads more than is counted here, and its share is
lower for it, as it should be.
"""

from __future__ import annotations


def window_attention_decode(context_tokens: float, lanes: float, window: int,
                            n_heads: int, latent_dim: int, value_dim: int,
                            itemsize: int = 2) -> dict[str, float]:
    """One call of decode attention of a window layer (one layer, one step):
    a lane attends to min(context, window) rows. The trace gives the lanes'
    contexts as a sum, so the rows are min(context_tokens, lanes * window):
    exact where every lane is on one side of the window (the cell's contexts
    are eight windows and more), an overcount of the rows only where long and
    short lanes mix.

    FLOPs: 2 * heads * (latent_dim + value_dim) per row attended to. Bytes:
    every such row once (latent_dim values), plus per lane the query (heads *
    latent_dim), the new row, and the output (heads * value_dim)."""
    rows = min(context_tokens, lanes * window)
    flops = 2.0 * n_heads * (latent_dim + value_dim) * rows
    row_bytes = latent_dim * itemsize * rows
    lane_bytes = lanes * itemsize * (n_heads * (latent_dim + value_dim)
                                     + latent_dim)
    return {"flops": flops, "bytes": row_bytes + lane_bytes}
