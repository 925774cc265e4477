"""What a call of the state-space layers' decode kernel needs: the
yardstick's operation and byte counts for `ssm_decode_roofline`, beside
kernels.py (whose peaks and `roofline_seconds` it is read with).

As there, the counts are what the ALGORITHM needs from its shapes: one step
of the recurrence `S <- S * keep + (dt x) (x) B; y = S C` reads every value
of a sequence's state once and writes it once, in float32, whatever the
number of heads a block. A padding lane (a row of the step that is nobody's)
is not counted: the kernel moves its rows all the same, which lowers the
share, as it should.
"""

from __future__ import annotations


def ssm_state_update(lanes: float, n_heads: int, head_dim: int, state: int,
                     n_groups: int, itemsize: int = 4) -> dict[str, float]:
    """One call of the kernel (one state layer, one step) over `lanes`
    sequences.

    FLOPs: a state value is scaled, takes its share of the outer product
    (one multiply to form it, one add) and enters y (a multiply and an add):
    6 a value, counting the product with `keep` and the one that forms the
    outer product once each.
    Bytes: the state in and out, plus per lane `keep` (heads), `dt x` and y
    (heads * head_dim each), `B` and `C` (groups * state each)."""
    values = lanes * n_heads * head_dim * state
    lane_bytes = lanes * itemsize * (n_heads + 2 * n_heads * head_dim
                                     + 2 * n_groups * state)
    return {"flops": 6.0 * values, "bytes": 2.0 * itemsize * values + lane_bytes}
