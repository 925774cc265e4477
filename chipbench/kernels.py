"""The yardstick for kernels: the chip's peaks, and what a kernel call needs.

Peaks are keyed by `device_kind` as JAX reports it; a device that is not in
the table is an error, not a default. Operation and byte counts are what the
ALGORITHM needs for the call, from its shapes: padding a kernel reads or
computes on top of that lowers its share, as it should.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       "chipbench/kernels.py with its source")
    return PEAKS[device_kind]


def paged_attention_decode(context_tokens: float, lanes: float, n_heads: int,
                           n_kv_heads: int, head_dim: int,
                           itemsize: int = 2) -> dict[str, float]:
    """One call of the paged decode-attention kernel (one layer, one step)
    over `lanes` sequences whose contexts sum to `context_tokens`.

    FLOPs: q.K^T and p.V, 2 * heads * head_dim each per context token.
    Bytes: every context token's K and V row once (kv_heads * head_dim each),
    plus per lane the query, the new K and V rows, and the output."""
    flops = 4.0 * n_heads * head_dim * context_tokens
    kv_bytes = 2.0 * n_kv_heads * head_dim * itemsize * context_tokens
    lane_bytes = lanes * itemsize * head_dim * (2 * n_heads + 2 * n_kv_heads)
    return {"flops": flops, "bytes": kv_bytes + lane_bytes}


def roofline_seconds(cost: dict[str, float], device_kind: str) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    p = peaks(device_kind)
    by_compute = cost["flops"] / p["flops_per_s"]
    by_memory = cost["bytes"] / p["bytes_per_s"]
    return ((by_compute, "compute") if by_compute >= by_memory
            else (by_memory, "memory"))


SHAPE_FUNCTIONS = {"paged_attention_decode": paged_attention_decode}
