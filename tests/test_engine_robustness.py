"""Engine robustness: aborts, stop handling, rejection, P/D edge cases."""

import asyncio

import httpx

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.request import FinishReason
from llm_d_inference_scheduler_tpu.engine.server import EngineServer


def run(coro):
    return asyncio.run(coro)


def _cfg(backend, port, **kw):
    kw.setdefault("model", "tiny")
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 128)
    return EngineConfig(backend=backend, port=port, **kw)


def test_abort_mid_decode_frees_blocks():
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(_cfg("tpu", 0))
        await eng.start()
        try:
            req = EngineRequest(request_id="long", prompt_token_ids=[1, 5, 6],
                                max_tokens=100, stop_token_ids=(99999,))
            out = eng.submit(req)
            ev = await asyncio.wait_for(out.get(), timeout=30)  # first token
            assert ev.token_id is not None
            eng.abort("long")
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=30)
                if ev.finish_reason is not None:
                    break
            assert ev.finish_reason == FinishReason.ABORT
            for _ in range(50):  # engine thread frees asynchronously
                if eng.allocator.free_blocks == eng.n_blocks - 1:
                    break
                await asyncio.sleep(0.05)
            assert eng.allocator.free_blocks == eng.n_blocks - 1
        finally:
            await eng.stop()

    run(body())


def test_impossible_request_rejected_not_wedged():
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        cfg = _cfg("tpu", 0, max_model_len=128, hbm_kv_blocks=3)
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            # Needs 8 blocks of 16, only 2 usable exist -> immediate abort.
            big = EngineRequest(request_id="big", prompt_token_ids=[1] * 100,
                                max_tokens=28)
            out = eng.submit(big)
            ev = await asyncio.wait_for(out.get(), timeout=10)
            assert ev.finish_reason == FinishReason.ABORT
            # Engine still serves normal requests afterwards.
            ok = EngineRequest(request_id="ok", prompt_token_ids=[1, 2, 3], max_tokens=2)
            out2 = eng.submit(ok)
            while True:
                ev = await asyncio.wait_for(out2.get(), timeout=30)
                if ev.finish_reason is not None:
                    break
            assert ev.finish_reason in (FinishReason.LENGTH, FinishReason.STOP)
        finally:
            await eng.stop()

    run(body())


def test_pd_import_block_count_exceeds_decode_allocation():
    """Exporter retained more blocks (prompt+16 default) than the decode side
    would allocate for max_tokens=1; import must still work."""
    async def body():
        pre = EngineServer(_cfg("tpu", 18321, role="prefill"))
        dec = EngineServer(_cfg("tpu", 18322, role="decode"))
        await pre.start()
        await dec.start()
        try:
            prompt = [1] + list(range(10, 23))  # 14 tokens: 1 block of 16...
            async with httpx.AsyncClient(timeout=60) as c:
                r1 = await c.post("http://127.0.0.1:18321/v1/completions", json={
                    "prompt": prompt,  # server default max_tokens=16 -> 2 blocks
                    "kv_transfer_params": {"do_remote_decode": True}})
                ktp = r1.json()["kv_transfer_params"]
                assert ktp["remote_num_blocks"] == 2
                r2 = await c.post("http://127.0.0.1:18322/v1/completions", json={
                    "prompt": prompt, "max_tokens": 1,
                    "kv_transfer_params": ktp})
                assert r2.status_code == 200
                assert r2.json()["usage"]["completion_tokens"] >= 1
        finally:
            await pre.stop()
            await dec.stop()

    run(body())


def test_stop_strings_and_stop_token_ids():
    async def body():
        cfg = _cfg("sim", 18323)
        server = EngineServer(cfg)
        await server.start()
        try:
            async with httpx.AsyncClient(base_url="http://127.0.0.1:18323",
                                         timeout=30) as c:
                # sim emits "lorem ipsum dolor ..." -> stop at "ipsum"
                r = await c.post("/v1/completions", json={
                    "prompt": "x", "max_tokens": 30, "stop": ["ipsum"]})
                body_ = r.json()
                assert body_["choices"][0]["finish_reason"] == "stop"
                assert "ipsum" not in body_["choices"][0]["text"]
                assert body_["choices"][0]["text"].startswith("lorem")
        finally:
            await server.stop()

    run(body())


def test_kv_export_ttl_sweep():
    async def body():
        from llm_d_inference_scheduler_tpu.engine import core as core_mod
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(_cfg("tpu", 0))
        old_ttl = core_mod.KV_EXPORT_TTL_S
        core_mod.KV_EXPORT_TTL_S = 0.2
        await eng.start()
        try:
            req = EngineRequest(request_id="exp", prompt_token_ids=[1, 2, 3],
                                max_tokens=1,
                                kv_transfer_params={"do_remote_decode": True})
            out = eng.submit(req)
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=30)
                if ev.finish_reason is not None:
                    break
            assert ev.kv_transfer_params is not None
            assert "exp" in eng.kv_exports
            await asyncio.sleep(0.5)
            # Submit another request so the engine loop runs a sweep.
            out2 = eng.submit(EngineRequest(request_id="poke",
                                            prompt_token_ids=[1, 2], max_tokens=1))
            while True:
                ev = await asyncio.wait_for(out2.get(), timeout=30)
                if ev.finish_reason is not None:
                    break
            assert "exp" not in eng.kv_exports
        finally:
            core_mod.KV_EXPORT_TTL_S = old_ttl
            await eng.stop()

    run(body())


def test_stream_stop_string_across_token_boundary():
    """Sim emits one char per token; a multi-char stop string must not leak
    its prefix into the SSE stream."""
    async def body():
        cfg = _cfg("sim", 18324)
        server = EngineServer(cfg)
        await server.start()
        try:
            async with httpx.AsyncClient(base_url="http://127.0.0.1:18324",
                                         timeout=30) as c:
                text = ""
                finish = None
                async with c.stream("POST", "/v1/completions", json={
                        "prompt": "x", "max_tokens": 30, "stream": True,
                        "stop": ["m ips"]}) as r:
                    async for line in r.aiter_lines():
                        if not line.startswith("data: ") or line == "data: [DONE]":
                            continue
                        import json as _json
                        doc = _json.loads(line[6:])
                        ch = doc["choices"][0]
                        text += ch.get("text", "")
                        if ch.get("finish_reason"):
                            finish = ch["finish_reason"]
                            assert doc["usage"]["prompt_tokens"] > 0
                assert finish == "stop"
                assert text == "lore", repr(text)  # truncated before "m ips"
        finally:
            await server.stop()

    run(body())


def test_over_context_prompt_rejected_400():
    """OpenAI/vLLM contract: a prompt that cannot fit the model context with
    at least one generated token is a 400, not a silently truncated serve."""
    async def body():
        srv = EngineServer(_cfg("tpu", 18467))
        await srv.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                r = await c.post("http://127.0.0.1:18467/v1/completions",
                                 json={"prompt": list(range(3, 131)),
                                       "max_tokens": 4})
                assert r.status_code == 400
                assert "maximum context length" in r.text

                # At the boundary (prompt + 1 generated == max_model_len): ok.
                r = await c.post("http://127.0.0.1:18467/v1/completions",
                                 json={"prompt": list(range(3, 130)),
                                       "max_tokens": 4, "ignore_eos": True})
                assert r.status_code == 200
                assert r.json()["usage"]["completion_tokens"] == 1
        finally:
            await srv.stop()

    run(body())


def test_mixed_admission_fuzz_batched_and_chunked():
    """Randomized mix of short/long prompts, mid-flight aborts, and varied
    max_tokens against an engine that writes prompts in 32-token windows
    (a short prompt in one, a long one a window a step) with prefix caching
    on: every request must terminate, and every block must come back."""
    import random

    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    rng = random.Random(11)

    async def body():
        eng = TpuEngine(_cfg("tpu", 0, max_batch=6, max_model_len=256,
                             decode_chunk=4, kv_events_port=0, seed=11,
                             prefill_chunk=32))
        await eng.start()
        outcomes = {"finished": 0, "aborted": 0}
        try:
            async def one(i):
                n_prompt = rng.choice([8, 30, 30, 90, 150])
                base = rng.randrange(3)  # some identical prompts → dedupe
                prompt = [1] + [(base * 131 + j * 7) % 400 + 3
                                for j in range(n_prompt)]
                req = EngineRequest(
                    request_id=f"fz{i}", prompt_token_ids=prompt,
                    max_tokens=rng.choice([1, 4, 9]), temperature=0.0,
                    ignore_eos=True)
                out = eng.submit(req)
                kill_after = rng.random() < 0.2
                got = 0
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=180)
                    if ev.token_id is not None:
                        got += 1
                        if kill_after and got == 1:
                            eng.abort(req.request_id)
                    if ev.finish_reason is not None:
                        key = ("aborted"
                               if ev.finish_reason == FinishReason.ABORT
                               else "finished")
                        outcomes[key] += 1
                        return

            # Three overlapping waves so admission sees bursts AND trickles.
            for wave in range(3):
                await asyncio.gather(*[one(wave * 20 + i) for i in range(20)])
            assert sum(outcomes.values()) == 60
            # Allocator fully drained (trash block excluded).
            free = getattr(eng.allocator, "reusable_blocks",
                           eng.allocator.free_blocks)
            assert free == eng.n_blocks - 1, (free, eng.n_blocks)
        finally:
            await eng.stop()
        assert outcomes["finished"] > 0

    run(body())


def test_sigterm_graceful_drain():
    """run_server's SIGTERM flow: readiness flips 503 immediately, the
    in-flight request still completes, then the server exits cleanly."""
    import os
    import signal

    from llm_d_inference_scheduler_tpu.engine.server import run_server

    async def body():
        cfg = _cfg("sim", 18341, sim_decode_ms_per_token=30.0)
        srv_task = asyncio.create_task(run_server(cfg, drain_timeout_s=20.0))
        async with httpx.AsyncClient(timeout=60) as c:
            for _ in range(100):  # wait for the listener
                if srv_task.done():
                    srv_task.result()  # surface the server's own exception
                    raise AssertionError("server exited before serving")
                try:
                    r = await c.get("http://127.0.0.1:18341/health")
                    if r.status_code == 200:
                        break
                except Exception:
                    pass  # httpx/httpcore connect errors while binding
                await asyncio.sleep(0.05)
            else:
                raise AssertionError("server never became healthy")

            # Long-ish request in flight, then SIGTERM mid-generation.
            gen = asyncio.create_task(c.post(
                "http://127.0.0.1:18341/v1/completions",
                json={"prompt": "hello", "max_tokens": 30}))
            await asyncio.sleep(0.2)
            os.kill(os.getpid(), signal.SIGTERM)
            await asyncio.sleep(0.3)
            r = await c.get("http://127.0.0.1:18341/health")
            assert r.status_code == 503
            assert r.json()["status"] == "draining"

            resp = await gen
            assert resp.status_code == 200
            assert resp.json()["usage"]["completion_tokens"] == 30
        await asyncio.wait_for(srv_task, timeout=30)

    run(body())


def test_chaos_shim_on_engine_surface():
    """The env/config-gated fault-injection shim (router/resilience.py via
    the EngineServer middleware): injected 503s carry the retryable
    x-removal-reason contract, decisions are deterministic per request id,
    and non-generate surfaces (health/metrics) are never chaos'd."""
    async def body():
        cfg = _cfg("sim", 18343, chaos="http503:50", chaos_seed=7)
        srv = EngineServer(cfg)
        await srv.start()
        try:
            async with httpx.AsyncClient(base_url="http://127.0.0.1:18343",
                                         timeout=30) as c:
                outcomes = {}
                for i in range(32):
                    r = await c.post("/v1/completions",
                                     json={"prompt": "x", "max_tokens": 1},
                                     headers={"x-request-id": f"det-{i}"})
                    outcomes[f"det-{i}"] = r.status_code
                assert set(outcomes.values()) == {200, 503}  # pct 50 splits
                for rid, status in outcomes.items():
                    r = await c.post("/v1/completions",
                                     json={"prompt": "x", "max_tokens": 1},
                                     headers={"x-request-id": rid})
                    assert r.status_code == status  # same id, same fate
                    if status == 503:
                        assert r.headers["x-removal-reason"] == "chaos-injected"
                # Control surfaces stay clean.
                assert (await c.get("/health")).status_code == 200
                assert (await c.get("/metrics")).status_code == 200
                # Runtime gate: disabling the injector heals everything.
                srv.chaos.enabled = False
                for rid in list(outcomes)[:8]:
                    r = await c.post("/v1/completions",
                                     json={"prompt": "x", "max_tokens": 1},
                                     headers={"x-request-id": rid})
                    assert r.status_code == 200
        finally:
            await srv.stop()

    run(body())


def test_drain_timeout_aborts_stragglers():
    """A request that cannot finish inside the drain window is actively
    aborted (ABORT event, not a hang into the SIGKILL window), and the
    server exits promptly."""
    import os
    import signal
    import time as _time

    from llm_d_inference_scheduler_tpu.engine.server import run_server

    async def body():
        # 200ms/token x 200 tokens >> the 1s drain window.
        cfg = _cfg("sim", 18342, sim_decode_ms_per_token=200.0)
        srv_task = asyncio.create_task(run_server(cfg, drain_timeout_s=1.0))
        async with httpx.AsyncClient(timeout=60) as c:
            for _ in range(100):
                if srv_task.done():
                    srv_task.result()
                    raise AssertionError("server exited before serving")
                try:
                    if (await c.get("http://127.0.0.1:18342/health")
                            ).status_code == 200:
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.05)
            gen = asyncio.create_task(c.post(
                "http://127.0.0.1:18342/v1/completions",
                json={"prompt": "hello", "max_tokens": 200}))
            await asyncio.sleep(0.3)
            t0 = _time.monotonic()
            os.kill(os.getpid(), signal.SIGTERM)
            resp = await gen  # aborted partial completion, not a hang
            assert resp.status_code == 200
            assert resp.json()["usage"]["completion_tokens"] < 200
            await asyncio.wait_for(srv_task, timeout=15)
            assert _time.monotonic() - t0 < 12  # 1s drain + bounded teardown

    run(body())


def test_drain_gate_waits_for_staged_kv_export():
    """SIGTERM drain must not tear down a prefill pod while a staged KV
    export is waiting for (or mid-way through) a decode peer's pull:
    idle() counts kv_exports and queued release requests."""
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(_cfg("tpu", 0, role="prefill"))
        await eng.start()
        try:
            assert eng.idle()
            req = EngineRequest(request_id="drain-exp",
                                prompt_token_ids=[1, 2, 3], max_tokens=1,
                                kv_transfer_params={"do_remote_decode": True})
            out = eng.submit(req)
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=30)
                if ev.finish_reason is not None:
                    break
            assert ev.kv_transfer_params is not None
            assert "drain-exp" in eng.kv_exports
            # The request finished, but the staged export pins the drain
            # gate: a decode peer may still be mid-pull.
            assert not eng.idle()
            # Release (decode peer finished its pull) -> drain may proceed.
            eng.release_kv_export("drain-exp")
            for _ in range(100):
                if eng.idle():
                    break
                await asyncio.sleep(0.05)
            assert eng.idle()
        finally:
            await eng.stop()

    run(body())


def test_chunk_streamed_export_record_shape_and_drain_gate():
    """Pipelined P/D: a ``stream_chunks`` prefill stages its KV incrementally
    into the export record (chunk_blocks/chunks_staged/blocks_staged/complete
    state machine, chunk data aligned with the counters), and the SIGTERM
    drain gate pins the chunk-staged export exactly like a legacy one — a
    decode peer may still be mid-chunk-stream."""
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(_cfg("tpu", 0, role="prefill", prefill_chunk=16))
        await eng.start()
        try:
            assert eng.idle()
            req = EngineRequest(
                request_id="chunk-exp",
                prompt_token_ids=list(range(3, 52)),  # 49 tokens, 4 blocks
                max_tokens=1,
                kv_transfer_params={"do_remote_decode": True,
                                    "stream_chunks": True})
            out = eng.submit(req)
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=30)
                if ev.finish_reason is not None:
                    break
            assert ev.kv_transfer_params is not None
            rec = eng.kv_exports["chunk-exp"]
            # Record shape: counters and staged data agree, and the record
            # reads complete exactly once finalized.
            assert rec["complete"] is True
            assert rec["chunks_staged"] >= 2  # 16-token windows really chunked
            assert len(rec["chunk_blocks"]) == rec["chunks_staged"]
            assert len(rec["chunk_data"]) == rec["chunks_staged"]
            assert sum(rec["chunk_blocks"]) == rec["blocks_staged"]
            assert rec["blocks_staged"] == rec["num_blocks"]
            for (k_np, v_np), cb in zip(rec["chunk_data"],
                                        rec["chunk_blocks"]):
                assert k_np.shape[1] == cb and v_np.shape[1] == cb
            # Reassembled chunk bytes == the legacy full-payload serve.
            import numpy as np
            k_all = np.concatenate([k for k, _ in rec["chunk_data"]], axis=1)
            assert k_all.shape[1] == rec["num_blocks"]
            assert np.array_equal(k_all, np.asarray(rec["k"]))
            # Drain gate: the chunk-staged export pins idle() until released.
            assert not eng.idle()
            eng.release_kv_export("chunk-exp")
            for _ in range(100):
                if eng.idle():
                    break
                await asyncio.sleep(0.05)
            assert eng.idle()
        finally:
            await eng.stop()

    run(body())


def test_partial_chunk_export_dropped_on_abort():
    """A chunk-streamed prefill aborted mid-stream must not leave a
    partially-staged (complete=False) export behind: the decode peer's next
    poll 404s (it degrades to local prefill) and the drain gate is not
    pinned forever by a record no peer will ever release."""
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(_cfg("tpu", 0, role="prefill", prefill_chunk=16))
        await eng.start()
        try:
            req = EngineRequest(
                request_id="chunk-abort",
                prompt_token_ids=list(range(3, 120)),
                max_tokens=1,
                kv_transfer_params={"do_remote_decode": True,
                                    "stream_chunks": True})
            out = eng.submit(req)
            # Abort while the prefill windows are still being written.
            eng.abort("chunk-abort")
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=30)
                if ev.finish_reason is not None:
                    break
            for _ in range(100):
                if eng.idle():
                    break
                await asyncio.sleep(0.05)
            # Whatever was staged before the abort is gone (incomplete
            # records are dropped; a COMPLETE export would be kept).
            rec = eng.kv_exports.get("chunk-abort")
            assert rec is None or rec.get("complete", True)
            assert eng.idle()
        finally:
            await eng.stop()

    run(body())
