"""E/PD encode disaggregation: multimodal requests prime encode workers."""

import asyncio

import httpx

from llm_d_inference_scheduler_tpu.engine import EngineConfig
from llm_d_inference_scheduler_tpu.engine.server import EngineServer
from llm_d_inference_scheduler_tpu.router.gateway import build_gateway
from llm_d_inference_scheduler_tpu.router.sidecar import Sidecar, SidecarConfig

GW, SC, DEC, PRE, ENC = 18460, 18461, 18462, 18463, 18464

CFG = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {SC}, labels: {{llm-d.ai/role: decode}}}}
    - {{address: 127.0.0.1, port: {PRE}, labels: {{llm-d.ai/role: prefill}}}}
    - {{address: 127.0.0.1, port: {ENC}, labels: {{llm-d.ai/role: encode}}}}
plugins:
  - {{type: decode-filter}}
  - {{type: prefill-filter}}
  - {{type: encode-filter}}
  - {{type: queue-scorer}}
  - {{type: approx-prefix-cache-producer}}
  - {{type: prefix-cache-scorer}}
  - type: disagg-profile-handler
    parameters:
      pdDecider:
        type: prefix-based-pd-decider
        parameters: {{thresholdTokens: 16}}
      encodeDecider: always-disagg-multimodal-decider
schedulingProfiles:
  - name: decode
    plugins:
      - {{pluginRef: decode-filter}}
      - {{pluginRef: queue-scorer}}
  - name: prefill
    plugins:
      - {{pluginRef: prefill-filter}}
      - {{pluginRef: queue-scorer}}
  - name: encode
    plugins:
      - {{pluginRef: encode-filter}}
      - {{pluginRef: queue-scorer}}
"""


def test_epd_encode_fanout():
    async def body():
        servers = [
            EngineServer(EngineConfig(backend="sim", model="tiny", port=p,
                                      role=role))
            for p, role in ((DEC, "decode"), (PRE, "prefill"), (ENC, "encode"))]
        for s in servers:
            await s.start()
        enc_server = servers[2]
        sc = Sidecar(SidecarConfig(port=SC, decoder_url=f"http://127.0.0.1:{DEC}",
                                   ssrf_allowlist=[f"127.0.0.1:{PRE}",
                                                   f"127.0.0.1:{ENC}"]))
        await sc.start()
        gw = build_gateway(CFG, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            long_text = "describe this image in detail please " * 4
            async with httpx.AsyncClient(timeout=60) as c:
                r = await c.post(f"http://127.0.0.1:{GW}/v1/chat/completions", json={
                    "model": "tiny", "max_tokens": 3,
                    "messages": [{"role": "user", "content": [
                        {"type": "text", "text": long_text},
                        {"type": "image_url", "image_url": {"url": "http://x/cat.png"}},
                        {"type": "image_url", "image_url": {"url": "http://x/dog.png"}},
                    ]}]})
                assert r.status_code == 200
                # encoder was primed with both items: one staged entry whose
                # embedding rows cover 2 images × n_patches each
                assert len(enc_server.ec_store) == 1
                (rec,) = enc_server.ec_store.values()
                from llm_d_inference_scheduler_tpu.models import TINY
                from llm_d_inference_scheduler_tpu.models.vision import VIT_TINY
                # Tower projects into the served model's d_model.
                assert rec["embeds"].shape == (2 * VIT_TINY.n_patches,
                                               TINY.d_model)
                assert rec["indices"] == [0, 1]

                m = await c.get(f"http://127.0.0.1:{GW}/metrics")
                assert 'decision_type="encode-prefill-decode"' in m.text

                # text-only request: no encode stage
                before = list(enc_server.ec_store)
                r = await c.post(f"http://127.0.0.1:{GW}/v1/chat/completions", json={
                    "model": "tiny", "max_tokens": 2,
                    "messages": [{"role": "user", "content": "plain text"}]})
                assert r.status_code == 200
                assert list(enc_server.ec_store) == before
        finally:
            await gw.stop()
            await sc.stop()
            for s in servers:
                await s.stop()

    asyncio.run(body())


def test_vision_tower_shapes_and_determinism():
    import jax
    import numpy as np

    from llm_d_inference_scheduler_tpu.models.vision import (
        VIT_TINY,
        encode_image,
        init_vision_params,
    )

    params = init_vision_params(VIT_TINY, jax.random.key(0))
    px = np.random.default_rng(0).standard_normal(
        (2, VIT_TINY.image_size, VIT_TINY.image_size, 3)).astype(np.float32)
    out = encode_image(params, VIT_TINY, px)
    assert out.shape == (2, VIT_TINY.n_patches, VIT_TINY.out_dim)
    out2 = encode_image(params, VIT_TINY, px)
    assert np.allclose(out, out2)
    # Different images → different embeddings.
    assert not np.allclose(out[0], out[1])


def test_epd_embeddings_reach_prefill_and_change_output():
    """Phase 2 (CPU encode, TPU prefill and decode): the encode worker's embeddings are
    pulled by the serving engine and spliced into prefill — two different
    images must produce different generations for the same text."""
    DEC2, ENC2, SC2 = 18470, 18471, 18472

    async def body():
        dec = EngineServer(EngineConfig(backend="tpu", model="tiny", port=DEC2,
                                        max_batch=4, max_model_len=256,
                                        kv_events_port=0))
        enc = EngineServer(EngineConfig(backend="sim", model="tiny", port=ENC2,
                                        role="encode"))
        await dec.start()
        await enc.start()
        sc = Sidecar(SidecarConfig(port=SC2, decoder_url=f"http://127.0.0.1:{DEC2}"))
        await sc.start()
        try:
            async def ask(image_seed):
                pixels = [[[float(image_seed)] * 3] * 4] * 4  # tiny 4x4 patch
                async with httpx.AsyncClient(timeout=90) as c:
                    r = await c.post(
                        f"http://127.0.0.1:{SC2}/v1/chat/completions",
                        json={"model": "tiny", "max_tokens": 6,
                              "temperature": 0, "ignore_eos": True,
                              "messages": [{"role": "user", "content": [
                                  {"type": "text", "text": "what is this?"},
                                  {"type": "image_url", "pixels": pixels},
                              ]}]},
                        headers={"x-encoder-hosts-ports": f"127.0.0.1:{ENC2}"})
                assert r.status_code == 200, r.text
                return r.json()["choices"][0]["message"]["content"]

            a = await ask(1.0)
            b = await ask(-3.0)
            plain = None
            async with httpx.AsyncClient(timeout=90) as c:
                r = await c.post(
                    f"http://127.0.0.1:{SC2}/v1/chat/completions",
                    json={"model": "tiny", "max_tokens": 6, "temperature": 0,
                          "ignore_eos": True,
                          "messages": [{"role": "user",
                                        "content": "what is this?"}]})
                plain = r.json()["choices"][0]["message"]["content"]
            # The injected embeddings must actually steer generation.
            assert a != b or a != plain
            assert len(a) > 0 and len(b) > 0
        finally:
            await sc.stop()
            await enc.stop()
            await dec.stop()

    asyncio.run(body())


def test_epd_item_order_preserved_across_hosts():
    """3 images round-robined over 2 encode hosts must splice back in the
    ORIGINAL order (indices ride the primer payload and the /ec response)."""
    DEC3, ENCA, ENCB, SC3 = 18475, 18476, 18477, 18478

    async def body():
        dec = EngineServer(EngineConfig(backend="tpu", model="tiny", port=DEC3,
                                        max_batch=4, max_model_len=256,
                                        kv_events_port=0))
        enc_a = EngineServer(EngineConfig(backend="sim", model="tiny",
                                          port=ENCA, role="encode"))
        enc_b = EngineServer(EngineConfig(backend="sim", model="tiny",
                                          port=ENCB, role="encode"))
        for s in (dec, enc_a, enc_b):
            await s.start()
        sc = Sidecar(SidecarConfig(port=SC3, decoder_url=f"http://127.0.0.1:{DEC3}"))
        await sc.start()
        try:
            import numpy as np

            def img(seed):
                return {"type": "image_url",
                        "pixels": [[[float(seed)] * 3] * 4] * 4}

            rid = "order-test-1"
            async with httpx.AsyncClient(timeout=90) as c:
                r = await c.post(
                    f"http://127.0.0.1:{SC3}/v1/chat/completions",
                    json={"model": "tiny", "max_tokens": 3, "temperature": 0,
                          "ignore_eos": True, "request_id": rid,
                          "messages": [{"role": "user", "content":
                                        [{"type": "text", "text": "see"}]
                                        + [img(s) for s in (1.0, 2.0, 3.0)]}]},
                    headers={"x-encoder-hosts-ports":
                             f"127.0.0.1:{ENCA},127.0.0.1:{ENCB}"})
            assert r.status_code == 200, r.text
            # Round-robin put images 0,2 on host A and 1 on host B.
            rec_a = enc_a.ec_store[rid]
            rec_b = enc_b.ec_store[rid]
            assert rec_a["indices"] == [0, 2]
            assert rec_b["indices"] == [1]

            # The reassembly the serving engine performs must restore global
            # order 0,1,2: A-rows[item0], B-rows[item1], A-rows[item2].
            _, mm, mm_pos = await dec._resolve_multimodal(
                {"request_id": rid,
                 "ec_sources": [f"127.0.0.1:{ENCA}", f"127.0.0.1:{ENCB}"]},
                [5, 6])
            per = rec_a["embeds"].shape[0] // 2
            expected = np.concatenate([rec_a["embeds"][:per],
                                       rec_b["embeds"],
                                       rec_a["embeds"][per:]])
            assert mm.shape == expected.shape
            assert np.allclose(mm, expected)
            assert mm_pos == list(range(mm.shape[0]))
        finally:
            await sc.stop()
            for s in (dec, enc_a, enc_b):
                await s.stop()

    asyncio.run(body())
