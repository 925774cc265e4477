"""What a call of the latent (MLA) decode-attention kernel needs: the
yardstick's operation and byte counts for `mla_decode_roofline`, beside
kernels.py (whose peaks and `roofline_seconds` it is read with).

As there, the counts are what the ALGORITHM needs from its shapes: the
absorbed form reads each cached row of `latent_dim` values once and uses it
as key and as value. The page pool stores a row padded to whole lanes (576 as
640); the padding is the layout's, read on top of what is counted here, and
lowers the share, as it should.
"""

from __future__ import annotations


def latent_attention_decode(context_tokens: float, lanes: float, n_heads: int,
                            latent_dim: int, value_dim: int,
                            itemsize: int = 2) -> dict[str, float]:
    """One call of the kernel (one layer, one step) over `lanes` sequences
    whose contexts sum to `context_tokens`.

    FLOPs: a head's query against all `latent_dim` values of a row, and the
    probabilities over the row's `value_dim` leading values: 2 * heads *
    (latent_dim + value_dim) per context token.
    Bytes: every context token's row once, plus per lane the query
    (heads * latent_dim), the new row, and the output (heads * value_dim)."""
    flops = 2.0 * n_heads * (latent_dim + value_dim) * context_tokens
    row_bytes = latent_dim * itemsize * context_tokens
    lane_bytes = lanes * itemsize * (n_heads * (latent_dim + value_dim)
                                     + latent_dim)
    return {"flops": flops, "bytes": row_bytes + lane_bytes}
