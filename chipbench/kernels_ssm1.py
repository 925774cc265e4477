"""What a call of the Mamba-1 layers' decode kernel needs: the yardstick's
operation and byte counts for `ssm1_decode_roofline`, beside kernels.py
(whose peaks and `roofline_seconds` it is read with) and kernels_ssm.py (the
Mamba-2 layers' kernel, another op).

As there, the counts are what the ALGORITHM needs from its shapes, whatever
implements it: one step of `S[n, c] <- exp(dt[c] A[n, c]) S[n, c] + dt[c] x[c]
B[n]; y[c] = sum_n S[n, c] C[n] + D[c] x[c]` reads every value of a
sequence's state once and writes it once, in float32. A padding lane (a row
of the step that is nobody's) is not counted: the kernel moves its rows all
the same, which lowers the share, as it should.
"""

from __future__ import annotations


def ssm1_state_update(lanes: float, channels: int, state: int,
                      itemsize: int = 4) -> dict[str, float]:
    """One call of the kernel (one state layer, one step) over `lanes`
    sequences of `channels` x `state` values each.

    Operations, 7 a value: the product `dt A`, the exponential (counted as
    one), the decay's product with the state, the outer product's own
    product and its add, and a multiply and an add into y; `dt x` and `D x`
    are a channel's, not a value's, and not counted.
    Bytes: the state in and out; per lane `dt`, `x` and y (`channels` each)
    and `B` and `C` (`state` each); `A` (`state` x `channels`) and `D`
    (`channels`) once a call."""
    values = lanes * channels * state
    lane_bytes = lanes * itemsize * (3 * channels + 2 * state)
    call_bytes = itemsize * (state * channels + channels)
    return {"flops": 7.0 * values,
            "bytes": 2.0 * itemsize * values + lane_bytes + call_bytes}
