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
