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
