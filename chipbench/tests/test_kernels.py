"""The shape function of the paged decode-attention kernel against numbers
worked by hand, and the table of peaks."""

import pytest

import kernels


def test_paged_attention_cost_by_hand():
    # Qwen3-4B: 32 query heads, 8 KV heads of 128, bf16. Ten lanes whose
    # contexts sum to 4,000 tokens.
    cost = kernels.paged_attention_decode(4000, 10, 32, 8, 128)
    # q.K^T and p.V: 2 * 32 * 128 FLOPs each per context token.
    assert cost["flops"] == 2 * (2 * 32 * 128) * 4000 == 65_536_000
    # K and V rows: 8 * 128 * 2 bytes each per context token = 4,096 B a token;
    # per lane q and out (32*128*2 B each) and the new K, V rows (8*128*2 each).
    assert cost["bytes"] == 4096 * 4000 + 10 * (2 * 8192 + 2 * 2048)
    least, bound = kernels.roofline_seconds(cost, "TPU v5 lite")
    assert bound == "memory"                  # 4 FLOPs a byte against 240
    assert least == pytest.approx(16_588_800 / 819e9)


def test_compute_bound_is_named():
    least, bound = kernels.roofline_seconds({"flops": 197e12, "bytes": 1.0},
                                            "TPU v5 lite")
    assert bound == "compute" and least == pytest.approx(1.0)


def test_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        kernels.peaks("cpu")
