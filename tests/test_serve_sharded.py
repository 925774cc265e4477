"""TP-sharded serving path (parallel/serve.py) on the virtual 8-CPU mesh.

Covers the driver's `dryrun_multichip` serving leg plus the engine running
with tp_size>1 end-to-end — the stepping stone to a 70B-class deployment
(TP-sharded decode). Reference analogue: vLLM's --tensor-parallel-size,
orchestrated but never implemented by the router (SURVEY §2.12).
"""

import asyncio

import jax
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
from llm_d_inference_scheduler_tpu.models import TINY
from llm_d_inference_scheduler_tpu.parallel.serve import (
    dryrun_serve,
    make_serve_mesh,
    validate_tp,
)


def test_dryrun_serve_matches_single_device():
    dryrun_serve(TINY, jax.devices()[:8], tp=2)


def test_validate_tp_rejects_bad_factor():
    with pytest.raises(ValueError):
        validate_tp(TINY, 3)  # n_kv_heads=2 not divisible


def test_make_serve_mesh_shape():
    mesh = make_serve_mesh(jax.devices()[:8], tp=2)
    assert mesh.shape == {"dp": 4, "tp": 2, "ep": 1}
    moe_mesh = make_serve_mesh(jax.devices()[:8], tp=2, ep=2)
    assert moe_mesh.shape == {"dp": 2, "tp": 2, "ep": 2}


def test_engine_tp_sharded_decode_matches_unsharded():
    """Same seed/request through tp=2 and tp=1 engines → identical tokens
    (greedy), proving the sharded serving jits are numerically faithful."""

    async def run(tp_size: int) -> list[int]:
        cfg = EngineConfig(model="tiny", max_batch=2, max_model_len=128,
                           tp_size=tp_size, enable_prefix_caching=False,
                           kv_events_port=0)
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            req = EngineRequest(
                request_id="tp-test",
                prompt_token_ids=[1] + [(i * 7) % 400 + 3 for i in range(40)],
                max_tokens=8, temperature=0.0, ignore_eos=True)
            out = eng.submit(req)
            toks = []
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=60)
                if ev.token_id is not None:
                    toks.append(ev.token_id)
                if ev.finish_reason is not None:
                    return toks
        finally:
            await eng.stop()

    sharded = asyncio.run(run(2))
    plain = asyncio.run(run(1))
    assert len(sharded) == 8 and len(plain) == 8
    # bf16 matmul reduction order differs across shardings, so a mid-stream
    # argmax tie-flip would cascade through the autoregressive tail — only the
    # first token is a stable cross-engine invariant here. The rigorous
    # numeric equivalence check (full logits, every step, f32) is
    # test_dryrun_serve_matches_single_device.
    assert sharded[0] == plain[0]


def test_engine_tp_rejects_invalid():
    with pytest.raises(ValueError):
        TpuEngine(EngineConfig(model="tiny", tp_size=3, kv_events_port=0))


def test_moe_serve_dryrun_tp_ep():
    from llm_d_inference_scheduler_tpu.models.configs import TINY_MOE

    dryrun_serve(TINY_MOE, jax.devices()[:8], tp=2, ep=2)


def test_moe_engine_serves_end_to_end():
    """tiny-moe through the full continuous-batching engine (the FFN hook
    covers prefill, paged decode, and prefix reuse unchanged)."""

    async def run() -> list[int]:
        cfg = EngineConfig(model="tiny-moe", max_batch=2, max_model_len=128,
                           kv_events_port=0)
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            req = EngineRequest(
                request_id="moe-test",
                prompt_token_ids=[1] + [(i * 5) % 400 + 3 for i in range(30)],
                max_tokens=6, temperature=0.0, ignore_eos=True)
            out = eng.submit(req)
            toks = []
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=60)
                if ev.token_id is not None:
                    toks.append(ev.token_id)
                if ev.finish_reason is not None:
                    return toks
        finally:
            await eng.stop()

    toks = asyncio.run(run())
    assert len(toks) == 6


def test_validate_ep_constraints():
    from llm_d_inference_scheduler_tpu.models.configs import TINY, TINY_MOE

    with pytest.raises(ValueError):
        validate_tp(TINY, 1, ep=2)       # dense model can't expert-shard
    with pytest.raises(ValueError):
        validate_tp(TINY_MOE, 1, ep=3)   # 4 experts % 3 != 0
    validate_tp(TINY_MOE, 2, ep=2)       # ok


def test_pipeline_forward_matches_single_device():
    from llm_d_inference_scheduler_tpu.parallel.pipeline import dryrun_pipeline

    dryrun_pipeline(TINY, jax.devices()[:2], pp=2, n_microbatches=4)


def test_pipeline_moe_and_bad_layer_split():
    from llm_d_inference_scheduler_tpu.models import llama
    from llm_d_inference_scheduler_tpu.models.configs import TINY_MOE
    from llm_d_inference_scheduler_tpu.parallel.pipeline import (
        dryrun_pipeline,
        make_pp_mesh,
        shard_params_pp,
    )

    dryrun_pipeline(TINY_MOE, jax.devices()[:2], pp=2, n_microbatches=2)
    # TINY has 2 layers: a 4-stage pipeline cannot split them evenly.
    mesh4 = make_pp_mesh(jax.devices()[:4], pp=4)
    params = llama.init_params(TINY, jax.random.key(0))
    with pytest.raises(ValueError):
        shard_params_pp(params, TINY, mesh4)


def test_engine_tp_sharded_qwen_decode():
    """tiny-qwen (QK-norm + head_dim override) through a tp=2 engine: the
    q_norm/k_norm params shard (replicated) and the decode-step hook runs
    under the tp shard_map."""

    async def run(tp_size: int) -> list[int]:
        cfg = EngineConfig(model="tiny-qwen", max_batch=2, max_model_len=128,
                           tp_size=tp_size, enable_prefix_caching=False,
                           kv_events_port=0)
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            req = EngineRequest(
                request_id="tp-qwen",
                prompt_token_ids=[1] + [(i * 5) % 400 + 3 for i in range(24)],
                max_tokens=6, temperature=0.0, ignore_eos=True)
            out = eng.submit(req)
            toks = []
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=120)
                if ev.token_id is not None:
                    toks.append(ev.token_id)
                if ev.finish_reason is not None:
                    return toks
        finally:
            await eng.stop()

    sharded = asyncio.run(run(2))
    plain = asyncio.run(run(1))
    assert len(sharded) == 6 and len(plain) == 6
    assert sharded[0] == plain[0]  # see bf16 tie-flip note above
