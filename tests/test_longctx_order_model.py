"""scripts/longctx_order_model.py: the model of the loop in device time that
chose `order` in chipbench/traffic/longctx-reason.json. It is held to the
chip's own readings of the default order (my chip runs, PR 41; PERF.md
section 6), and the pinned order has to come out steadier than the default."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))
import longctx_order_model as model  # noqa: E402

MIX = model.traffic.load_mix(model.traffic.mix_path(ROOT, "longctx-reason"))

# start of the cycle -> (out_tokens_per_s, tpot_p95_ms) read on the chip under
# `order` 0, the seeds 4115000053, 4102000013, 4116000067, 4113000029,
# 4114000041, 4112000017, 4111000003.
MEASURED = {3: (747.941, 45.166), 4: (753.686, 45.366), 8: (749.922, 45.529),
            13: (771.078, 43.190), 18: (760.235, 42.643), 40: (711.686, 45.226),
            45: (717.392, 45.030)}


@pytest.mark.parametrize("offset", sorted(MEASURED))
def test_model_reads_what_the_chip_read_under_the_default_order(offset):
    prompts, outputs = model.shapes(MIX, 0)
    run = model.simulate(MIX, prompts, outputs, offset, 51.0)
    tokens, tpot = MEASURED[offset]
    assert run["out_tokens_per_s"] == pytest.approx(tokens, rel=0.015)
    assert run["tpot_p95_ms"] == pytest.approx(tpot, rel=0.015)
    assert 28 <= run["requests"] <= 80


def test_pinned_order_is_steadier_than_the_default_over_every_start():
    spread = {}
    for order in (0, MIX["order"]):
        runs = model.starts(MIX, order, 51.0)
        spread[order] = [model.relative_sd([r[name] for r in runs])
                         for name in ("out_tokens_per_s", "tpot_p95_ms")]
        assert all(28 <= r["requests"] <= 80 for r in runs)
    tokens, tpot = spread[MIX["order"]]
    # A quarter of each bound: six runs then spread about half of it.
    assert tokens < 0.01 and tpot < 0.0125
    assert tokens < spread[0][0] / 2 and tpot < spread[0][1] / 2


# ---------- longctx-wide (PR 45): the same model at that cell's times ----------

@pytest.fixture
def wide():
    model.use("longctx-wide")
    yield model.traffic.load_mix(model.traffic.mix_path(ROOT, "longctx-wide"))
    model.use("longctx-reason")


def test_model_reads_the_wide_cells_first_run(wide):
    """My chip run, PR 45, seed 3000000011 under `order` 0 (start 57 of the 96
    shapes): 1,576.04 tokens/s, tpot_p95_ms 36.337, 75 requests."""
    prompts, outputs = model.shapes(wide, 0)
    run = model.simulate(wide, prompts, outputs, 57, 51.0)
    assert run["out_tokens_per_s"] == pytest.approx(1576.04, rel=0.02)
    assert run["tpot_p95_ms"] == pytest.approx(36.337, rel=0.02)
    assert 60 <= run["requests"] <= 90


def test_the_wide_cells_pinned_order_is_steadier_than_the_default(wide):
    spread = {}
    for order in (0, wide["order"]):
        runs = model.starts(wide, order, 51.0)
        spread[order] = [model.relative_sd([r[name] for r in runs])
                         for name in ("out_tokens_per_s", "tpot_p95_ms")]
    tokens, tpot = spread[wide["order"]]
    # A quarter of each bound: six runs then spread about half of it.
    assert tokens < 0.005 and tpot < 0.00625
    assert tokens < spread[0][0]


# ---------- longctx-16k (PR 48): the same model at that cell's times ----------

@pytest.fixture
def long16k():
    model.use("longctx-16k")
    yield model.traffic.load_mix(model.traffic.mix_path(ROOT, "longctx-16k"))
    model.use("longctx-reason")


# start of the cycle -> (out_tokens_per_s, tpot_p95_ms) read on the chip, my
# chip runs, PR 48: under `order` 37059 the seeds 2148000101 ... 606 (each
# deterministic: two of them run again read the same to the last digit),
# under `order` 0 the seed 3000000011, under the pinned `order` six more.
MEASURED_16K = {(37059, 51): (1198.745, 25.343), (37059, 30): (1250.275, 25.312),
                (37059, 84): (1239.510, 25.101), (37059, 83): (1250.824, 25.080),
                (37059, 32): (1244.039, 25.083), (37059, 61): (1215.451, 25.093),
                (0, 57): (1227.431, 25.318),
                # Predicted before they were run (seeds 2148010111 ... 666).
                (84274, 93): (1216.765, 25.116), (84274, 3): (1204.824, 24.938),
                (84274, 32): (1202.373, 25.002), (84274, 73): (1232.667, 25.101),
                (84274, 58): (1229.098, 25.314), (84274, 17): (1221.000, 25.074)}


@pytest.mark.parametrize("order, offset", sorted(MEASURED_16K))
def test_model_reads_what_the_chip_read_of_the_16k_cell(long16k, order, offset):
    """Under this mix's `DRAWN_AS_CLIENT` (shapes to arrivals as the client
    hands them out); in arrival order, as the two older mixes are still
    read, the model read these six starts of one order alike, 1,216-1,223,
    where the chip read 1,199-1,251."""
    prompts, outputs = model.shapes(long16k, order)
    run = model.simulate(long16k, prompts, outputs, offset, 51.0)
    tokens, tpot = MEASURED_16K[order, offset]
    assert run["out_tokens_per_s"] == pytest.approx(tokens, rel=0.006)
    assert run["tpot_p95_ms"] == pytest.approx(tpot, rel=0.008)
    assert 40 <= run["requests"] <= 80


def test_the_16k_cells_pinned_order_is_steadier_than_the_default(long16k):
    spread = {}
    for order in (0, long16k["order"]):
        runs = model.starts(long16k, order, 51.0)
        spread[order] = [model.relative_sd([r[name] for r in runs])
                         for name in ("out_tokens_per_s", "tpot_p95_ms")]
    tokens, tpot = spread[long16k["order"]]
    # Under half of each half bound (2% and 2.5%); of 100,000 shuffles none
    # read under 0.88% in tokens/s.
    assert tokens < 0.01 and tpot < 0.0125
    assert tokens < spread[0][0] * 0.7 and tpot < spread[0][1] * 0.7
