"""Engine tests: continuous batching core, HTTP surface, telemetry, P/D handoff."""

import asyncio
import collections
import contextlib
import functools
import json
import signal
import time
import types

import httpx
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.core import (
    HOLD_MARGIN_S,
    KEEP_UP_S,
    SHORT_CHUNK_DIVS,
)
from llm_d_inference_scheduler_tpu.engine.server import EngineServer


def run(coro):
    return asyncio.run(coro)


def _cfg(backend, port, **kw):
    kw.setdefault("model", "tiny")
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 128)
    return EngineConfig(backend=backend, port=port, **kw)


# ---------- TpuEngine core (runs on CPU backend via conftest) ----------

def test_tpu_engine_generates_and_batches():
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(_cfg("tpu", 0))
        await eng.start()
        try:
            reqs = [EngineRequest(request_id=f"r{i}", prompt_token_ids=[1] + [10 + i] * 5,
                                  max_tokens=6) for i in range(3)]
            outs = [eng.submit(r) for r in reqs]

            async def drain(out):
                evs = []
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=30)
                    evs.append(ev)
                    if ev.finish_reason is not None:
                        return evs

            results = await asyncio.gather(*[drain(o) for o in outs])
            for r, evs in zip(reqs, results):
                toks = [e.token_id for e in evs if e.token_id is not None]
                assert 1 <= len(toks) <= r.max_tokens
                assert evs[-1].finish_reason is not None
            # all blocks returned
            assert eng.allocator.free_blocks == eng.n_blocks - 1
        finally:
            await eng.stop()

    run(body())


def test_tpu_engine_greedy_matches_across_batching():
    """The same prompt decoded alone and alongside others yields the same tokens
    (continuous batching must not change results; greedy, f32-tolerant)."""
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(_cfg("tpu", 0))
        await eng.start()
        try:
            prompt = [1] + [42, 17, 9] * 3

            async def gen(rid, prompt):
                out = eng.submit(EngineRequest(request_id=rid, prompt_token_ids=prompt,
                                               max_tokens=5))
                toks = []
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=30)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.finish_reason is not None:
                        return toks

            solo = await gen("solo", prompt)
            batched = await asyncio.gather(
                gen("a", prompt), gen("b", [1, 99, 98, 97]), gen("c", prompt))
            assert batched[0] == solo and batched[2] == solo
        finally:
            await eng.stop()

    run(body())


# ---------- HTTP surface (sim backend) ----------

def test_sim_server_openai_surface():
    async def body():
        cfg = _cfg("sim", 18301)
        server = EngineServer(cfg)
        await server.start()
        try:
            async with httpx.AsyncClient(base_url="http://127.0.0.1:18301") as c:
                r = await c.post("/v1/completions",
                                 json={"model": "tiny", "prompt": "hello", "max_tokens": 4})
                assert r.status_code == 200
                body_ = r.json()
                assert body_["choices"][0]["finish_reason"] == "length"
                assert body_["usage"]["completion_tokens"] == 4

                r = await c.post("/v1/chat/completions", json={
                    "model": "tiny",
                    "messages": [{"role": "user", "content": "hi"}], "max_tokens": 3})
                assert r.json()["choices"][0]["message"]["role"] == "assistant"

                r = await c.get("/v1/models")
                assert r.json()["data"][0]["id"] == "tiny"

                r = await c.post("/v1/completions/render", json={"prompt": "abc"})
                assert len(r.json()["token_ids"]) == 4  # BOS + 3 bytes

                r = await c.get("/metrics")
                text = r.text
                for name in ("jetstream:num_requests_waiting",
                             "jetstream:num_requests_running",
                             "jetstream:kv_cache_usage_perc",
                             "jetstream:cache_config_info",
                             "jetstream:lora_requests_info"):
                    assert name in text, f"missing metric {name}"

                # streaming
                async with c.stream("POST", "/v1/completions",
                                    json={"prompt": "s", "max_tokens": 3,
                                          "stream": True}) as r:
                    chunks = []
                    async for line in r.aiter_lines():
                        if line.startswith("data: "):
                            chunks.append(line[6:])
                    assert chunks[-1] == "[DONE]"
                    assert len(chunks) >= 4  # 3 tokens + final + DONE
        finally:
            await server.stop()

    run(body())


# ---------- P/D KV handoff between two real engines ----------

def test_pd_handoff_between_tpu_engines():
    """Prefill on engine A with do_remote_decode, decode on engine B importing
    A's KV over HTTP; result must equal a monolithic decode on one engine."""
    async def body():
        prompt = [1] + [33, 44, 55] * 4
        max_tokens = 6

        mono = EngineServer(_cfg("tpu", 18311))
        await mono.start()
        try:
            async with httpx.AsyncClient() as c:
                r = await c.post("http://127.0.0.1:18311/v1/completions",
                                 json={"prompt": prompt, "max_tokens": max_tokens,
                                       "temperature": 0},
                                 timeout=60)
                mono_text = r.json()["choices"][0]["text"]
        finally:
            await mono.stop()

        pre = EngineServer(_cfg("tpu", 18312, role="prefill"))
        dec = EngineServer(_cfg("tpu", 18313, role="decode"))
        await pre.start()
        await dec.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                r1 = await c.post("http://127.0.0.1:18312/v1/completions", json={
                    "prompt": prompt, "max_tokens": 1, "stream": False,
                    "temperature": 0,
                    "kv_transfer_params": {"do_remote_decode": True}})
                assert r1.status_code == 200
                ktp = r1.json()["kv_transfer_params"]
                assert ktp["remote_seq_len"] == len(prompt)

                r2 = await c.post("http://127.0.0.1:18313/v1/completions", json={
                    "prompt": prompt, "max_tokens": max_tokens,
                    "temperature": 0, "kv_transfer_params": ktp})
                assert r2.status_code == 200
                disagg_text = r2.json()["choices"][0]["text"]
                assert disagg_text == mono_text
                # export released after pull
                assert not pre.engine.kv_exports
        finally:
            await pre.stop()
            await dec.stop()

    run(body())


def test_engine_warmup_compiles_before_serving():
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(_cfg("tpu", 0, warmup=True))
        assert eng.warming
        await eng.start()
        try:
            # warm-up must complete and not corrupt state: a normal request
            # works afterwards and all blocks stay accounted for.
            out = eng.submit(EngineRequest(request_id="w", prompt_token_ids=[1, 2, 3],
                                           max_tokens=2, ignore_eos=True))
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=120)
                if ev.finish_reason is not None:
                    break
            assert not eng.warming  # warm-up ran (serving happens after it)
            assert ev.finish_reason.value == "length"
            for _ in range(50):
                if eng.allocator.free_blocks == eng.n_blocks - 1:
                    break
                await asyncio.sleep(0.05)
            assert eng.allocator.free_blocks == eng.n_blocks - 1
        finally:
            await eng.stop()

    run(body())


def test_incremental_prefill_token_parity_and_no_stall():
    """prefill_chunk: a long prompt prefills in block-aligned windows, one
    per engine step, interleaved with other lanes. Greedy tokens must match
    whole-prompt prefill exactly; the warm rerun prefix-hits the deferred
    commit; and a short request admitted alongside a long one gets its
    first token BEFORE the long one (whole-prompt prefill would serve the
    long prompt's token first) — the observable no-stall property."""
    import asyncio
    import time as _time

    from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    LONG = [1] + [(j * 17) % 450 + 3 for j in range(120)]
    SHORT = [1] + [(j * 5) % 450 + 3 for j in range(30)]
    base = dict(model="tiny", backend="tpu", max_batch=4, max_model_len=256,
                decode_chunk=4, kv_events_port=0, seed=7)

    async def serve(cfg):
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            first_at: dict[str, float] = {}

            async def one(rid, prompt, n):
                out = eng.submit(EngineRequest(
                    request_id=rid, prompt_token_ids=list(prompt),
                    max_tokens=n, temperature=0.0, ignore_eos=True))
                toks, cached = [], 0
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=180)
                    if ev.token_id is not None:
                        if rid not in first_at:
                            first_at[rid] = _time.monotonic()
                        toks.append(ev.token_id)
                        cached = max(cached, ev.cached_tokens or 0)
                    if ev.finish_reason is not None:
                        return toks, cached

            # LONG submitted first: whole-prompt prefill serves it first;
            # incremental prefill lets SHORT through between windows.
            (lt, _), (st, _) = await asyncio.gather(
                one("L", LONG, 6), one("S", SHORT, 12))
            return lt, st, first_at
        finally:
            await eng.stop()

    lt_w, st_w, order_w = asyncio.run(serve(EngineConfig(**base)))
    lt_c, st_c, order_c = asyncio.run(serve(
        EngineConfig(**base, prefill_chunk=32)))
    assert (lt_c, st_c) == (lt_w, st_w)
    assert order_w["L"] <= order_w["S"]   # whole prefill: long lands first
    assert order_c["S"] < order_c["L"]    # chunked: short slips through

    async def warm_rerun():
        # warmup=True also exercises the chunked-shape precompile ladder.
        eng = TpuEngine(EngineConfig(**base, prefill_chunk=32, warmup=True))
        await eng.start()
        try:
            async def one(rid):
                out = eng.submit(EngineRequest(
                    request_id=rid, prompt_token_ids=list(LONG),
                    max_tokens=6, temperature=0.0, ignore_eos=True))
                toks, cached = [], 0
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=180)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                        cached = max(cached, ev.cached_tokens or 0)
                    if ev.finish_reason is not None:
                        return toks, cached

            a, _ = await one("a")
            b, cached = await one("b")
            return a, b, cached
        finally:
            await eng.stop()

    a, b, cached = asyncio.run(warm_rerun())
    assert a == b == lt_w
    assert cached >= 112  # 7 complete blocks committed by the chunked path



def test_prefill_windows_are_served_by_arrival_not_by_slot():
    """Three slots, windows of 16: A (short) holds slot 0 and ends at once,
    B and C (ten windows each) wait in slots 1 and 2; D (three windows)
    arrives when A is done and takes slot 0. Served by slot index D would
    overtake C (and the rest of B); served by arrival the first tokens come
    in the order B, C, D."""
    import time

    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    def prompt(salt, n):
        return [1] + [(j * salt) % 450 + 3 for j in range(n - 1)]

    async def body():
        eng = TpuEngine(EngineConfig(
            model="tiny", backend="tpu", max_batch=3, max_model_len=256,
            decode_chunk=2, prefill_chunk=16, kv_events_port=0, seed=3))
        await eng.start()
        first_at = {}

        async def one(rid, ids, n):
            out = eng.submit(EngineRequest(
                request_id=rid, prompt_token_ids=ids, max_tokens=n,
                temperature=0.0, ignore_eos=True))
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=300)
                if ev.token_id is not None:
                    first_at.setdefault(rid, time.monotonic())
                if ev.finish_reason is not None:
                    return

        try:
            a = asyncio.ensure_future(one("A", prompt(5, 12), 1))
            b = asyncio.ensure_future(one("B", prompt(7, 160), 2))
            c = asyncio.ensure_future(one("C", prompt(11, 160), 2))
            await a
            await one("D", prompt(13, 40), 2)
            await asyncio.gather(b, c)
        finally:
            await eng.stop()
        return first_at

    first_at = asyncio.run(body())
    assert first_at["B"] < first_at["C"] < first_at["D"]


@pytest.mark.parametrize("n_long, step_tokens, most", [
    (1, 4096, 1),   # a lone long prompt: one window a step, as ever
    (3, 4096, 3),   # three lanes wait for their prompts: a window each
    (3, 32, 2),     # ... within the step's budget of prompt tokens
])
def test_prefill_windows_a_step_follow_the_waiting_lanes(
        monkeypatch, n_long, step_tokens, most):
    """Windows of 16 tokens. A step writes one window for every slot still
    prefilling, at most PREFILL_STEP_TOKENS of prompt, oldest request first;
    the tokens are those of whole-prompt prefill."""
    from llm_d_inference_scheduler_tpu.engine import core
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    monkeypatch.setattr(core, "PREFILL_STEP_TOKENS", step_tokens)
    prompts = [[1] + [(j * salt) % 450 + 3 for j in range(95)]
               for salt in (7, 11, 13)[:n_long]]
    base = dict(model="tiny", backend="tpu", max_batch=4, max_model_len=256,
                decode_chunk=2, kv_events_port=0, seed=5)

    async def serve(cfg):
        eng = TpuEngine(cfg)
        steps: list[list[int]] = []
        advance, write = eng._advance_prefills, eng._write_prefill_window

        def counted_advance():
            steps.append([])
            advance()
            steps.append(None)      # what follows is no window of this step

        def counted_write(idx):
            if steps and steps[-1] is not None:
                steps[-1].append(idx)
            write(idx)      # (else a prompt of one window, at its admission)

        eng._advance_prefills = counted_advance
        eng._write_prefill_window = counted_write
        await eng.start()
        try:
            async def one(i, ids):
                out = eng.submit(EngineRequest(
                    request_id=f"r{i}", prompt_token_ids=ids, max_tokens=4,
                    temperature=0.0, ignore_eos=True))
                toks = []
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=300)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.finish_reason is not None:
                        return toks
            toks = await asyncio.gather(
                *(one(i, ids) for i, ids in enumerate(prompts)))
        finally:
            await eng.stop()
        return toks, [s for s in steps if s]

    whole, _ = asyncio.run(serve(EngineConfig(**base)))
    chunked, steps = asyncio.run(serve(EngineConfig(**base, prefill_chunk=16)))
    assert chunked == whole
    assert sum(map(len, steps)) == 6 * n_long     # 96 tokens: six windows each
    assert max(map(len, steps)) == most
    # Oldest first: a step never leaves an older request's window undone to
    # write a younger one's.
    order = [idx for s in steps for idx in s]
    assert order == sorted(order, key=order.index)


def test_note_kv_import_dedupes_eviction_ring():
    """A re-dispatched request id overwrites its kv_import_stats entry; the
    eviction ring must not gain a duplicate slot, or a later cap eviction
    pops the LIVE entry when the stale first occurrence reaches the front
    (the decode response then silently loses its x-kv-pull-ms stamp)."""
    import collections
    import time as _time

    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    class Stub:
        KV_IMPORT_STATS_CAP = TpuEngine.KV_IMPORT_STATS_CAP

    s = Stub()
    s.kv_import_stats = {}
    s._kv_import_order = collections.deque()
    for _ in range(3):
        TpuEngine._note_kv_import(s, "r1", _time.monotonic(), 10, "host")
    assert len(s._kv_import_order) == 1
    assert s.kv_import_stats["r1"]["bytes"] == 10


# ---------- one chunk in flight (ISSUE 33) ----------
# The engine dispatches chunk n + 1 before it reads chunk n. These tests drive
# TpuEngine._step() from their own thread (the engine's thread never starts),
# so every run takes the same steps, and compare what is served with what the
# parent commit (b0e8642: dispatch, wait, book, only then the next step)
# served for the same requests under the same driver. _PARENT_SERVED holds
# that commit's answers, as `python tests/test_engine.py` prints them when run
# with PYTHONPATH at a checkout of it (XLA_FLAGS and the platform as
# tests/conftest.py sets them); _STOP holds the stop tokens, which are tokens
# that the parent served in those places without a stop.

_CHUNK = 4


def _tiny_f32():
    import jax
    import jax.numpy as jnp

    from llm_d_inference_scheduler_tpu.models import llama
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    return llama.init_params(get_config("tiny"), jax.random.key(11),
                             dtype=jnp.float32)


def _prompt(salt, n):
    return [1] + [(j * salt) % 450 + 3 for j in range(n - 1)]


def _req(rid, prompt, max_tokens, temperature, stop=None):
    return EngineRequest(
        request_id=rid, prompt_token_ids=prompt, max_tokens=max_tokens,
        temperature=temperature, ignore_eos=True,
        stop_token_ids=() if stop is None else (stop,))


def _by_hand(requests, *, submit_at=None, abort_at=None, model="tiny",
             watch=None, **cfg):
    """Serve ``requests`` by calling _step() by hand. ``submit_at`` /
    ``abort_at``: request id -> the step before which it is submitted (0
    unless named) / aborted. Returns (tokens by id, finish reason by id, the
    engine). ``model``: `tiny` in float32, or a registered name on its own
    seeded weights. ``watch(eng, step)`` is called once with step None when
    the engine is built, then before every step."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    submit_at, abort_at = submit_at or {}, abort_at or {}
    cfg.setdefault("max_batch", 4)

    async def body():
        eng = TpuEngine(EngineConfig(
            model=model, backend="tpu", max_model_len=128,
            decode_chunk=_CHUNK, seed=11, kv_events_port=0, **cfg),
            params=_tiny_f32() if model == "tiny" else None)
        outs, toks, why = {}, {}, {}
        if watch is not None:
            watch(eng, None)
        for step in range(400):
            for r in requests:
                if submit_at.get(r.request_id, 0) == step:
                    outs[r.request_id] = eng.submit(r)
                    toks[r.request_id] = []
                if abort_at.get(r.request_id) == step:
                    eng.abort(r.request_id)
            if watch is not None:
                watch(eng, step)
            eng._step()
            await asyncio.sleep(0)      # the events hop onto this loop
            for rid, out in outs.items():
                while not out.empty():
                    ev = out.get_nowait()
                    if ev.token_id is not None:
                        toks[rid].append(ev.token_id)
                    if ev.finish_reason is not None:
                        why[rid] = ev.finish_reason.value
            if (len(why) == len(requests)
                    and getattr(eng, "_inflight", None) is None):
                return toks, why, eng
        raise AssertionError(f"not served in 400 steps: {why}")

    return asyncio.run(body())


def _mixed(t, stop, late=False):
    """Four requests on four lanes from step 0. D, in the top lane, ends on a
    stop token in its second chunk while A, B and C go on (so the chunk in
    flight, with D's lane dead in it, has the parent's shape and the parent's
    lanes 0 to 2); A and B end on max_tokens in the middle of a chunk, C at
    the end of one. ``late``: E arrives before step 3 and takes D's slot when
    it is free (greedy only: a lane joins the chunk dispatched right behind
    its prefill, the parent's joined the one after, and a sampled token
    depends on which chunk's key drew it)."""
    reqs = [_req("A", _prompt(5, 9), 11, t), _req("B", _prompt(7, 20), 18, t),
            _req("C", _prompt(11, 33), 25, t),
            _req("D", _prompt(13, 14), 40, t, stop)]
    if late:
        reqs.append(_req("E", _prompt(17, 12), 9, t))
    return dict(requests=reqs, submit_at={"E": 3})


def _first_is_stop(t, stop):
    """F's first token, the prefill's, is its stop token: the chunk
    dispatched behind that prefill carries F's lane for nothing. G decodes
    beside it."""
    return dict(requests=[_req("G", _prompt(19, 10), 10, t),
                          _req("F", _prompt(23, 21), 12, t, stop)])


def _aborted(t, stop=None):
    """D, in the top lane, is aborted before step 3, with a chunk that holds
    its lane in flight."""
    plan = _mixed(t, None)
    plan["abort_at"] = {"D": 3}
    return plan


def _reused(t, stop, **cfg):
    """Two lanes, nine usable blocks, prefix caching on. A (4 blocks) ends on
    a stop token with a chunk in flight whose overshoot lands in A's tail
    blocks; C, waiting with A's prompt, is admitted next, hits A's two parked
    prompt blocks and takes freed ones for its tail, its prefill queued
    behind that chunk; E follows with another prompt. B decodes throughout."""
    shared = _prompt(29, 40)
    return dict(requests=[_req("A", shared, 24, t, stop),
                          _req("B", _prompt(31, 37), 27, t),
                          _req("C", shared, 24, t),
                          _req("E", _prompt(37, 35), 13, t)],
                max_batch=2, hbm_kv_blocks=10, enable_prefix_caching=True,
                **cfg)


_PLANS = {"mixed": _mixed,
          "mixed-late": lambda t, stop: _mixed(t, stop, late=True),
          "first-is-stop": _first_is_stop, "aborted": _aborted,
          "reused": _reused}
# (plan, temperature) -> whose token is the stop token there, and which: the
# token the parent served in that place when nothing stopped it.
_STOP_AT = {("mixed", 0.0): ("D", 6), ("mixed", 0.8): ("D", 6),
            ("mixed-late", 0.0): ("D", 6), ("first-is-stop", 0.0): ("F", 0),
            ("first-is-stop", 0.8): ("F", 0), ("aborted", 0.0): None,
            ("aborted", 0.8): None, ("reused", 0.0): ("A", 7)}
_STOP = {('first-is-stop', 0.0): 187,
 ('first-is-stop', 0.8): 4,
 ('mixed', 0.0): 213,
 ('mixed', 0.8): 166,
 ('mixed-late', 0.0): 213,
 ('reused', 0.0): 378}
_PARENT_SERVED = {('aborted', 0.0): ({'A': [499, 364, 339, 18, 183, 8, 391, 339, 80, 466, 159],
                     'B': [343, 244, 303, 280, 115, 489, 268, 511, 255, 476, 3, 464, 16, 22, 357,
                           327, 445, 327],
                     'C': [141, 21, 280, 66, 257, 65, 324, 253, 102, 280, 64, 280, 292, 246, 219,
                           334, 395, 479, 56, 6, 416, 218, 342, 429, 187],
                     'D': [92, 97, 489, 174, 292, 259, 213, 184, 78]},
                    {'A': 'length', 'B': 'length', 'C': 'length', 'D': 'abort'}),
 ('aborted', 0.8): ({'A': [461, 19, 351, 481, 465, 377, 294, 378, 309, 210, 378],
                     'B': [359, 282, 50, 167, 489, 394, 163, 370, 285, 343, 201, 406, 110, 451, 209,
                           105, 99, 424],
                     'C': [48, 245, 494, 375, 195, 123, 36, 97, 451, 96, 294, 114, 369, 283, 374,
                           280, 311, 323, 479, 285, 311, 91, 143, 83, 508],
                     'D': [264, 285, 345, 472, 405, 140, 166, 330, 315]},
                    {'A': 'length', 'B': 'length', 'C': 'length', 'D': 'abort'}),
 ('first-is-stop', 0.0): ({'F': [187], 'G': [118, 212, 204, 97, 76, 280, 312, 292, 280, 118]},
                          {'F': 'stop', 'G': 'length'}),
 ('first-is-stop', 0.8): ({'F': [4], 'G': [477, 219, 424, 108, 125, 199, 25, 457, 314, 19]},
                          {'F': 'stop', 'G': 'length'}),
 ('mixed', 0.0): ({'A': [499, 364, 339, 18, 183, 8, 391, 339, 80, 466, 159],
                   'B': [343, 244, 303, 280, 115, 489, 268, 511, 255, 476, 3, 464, 16, 22, 357, 327,
                         445, 327],
                   'C': [141, 21, 280, 66, 257, 65, 324, 253, 102, 280, 64, 280, 292, 246, 219, 334,
                         395, 479, 56, 6, 416, 218, 342, 429, 187],
                   'D': [92, 97, 489, 174, 292, 259]},
                  {'A': 'length', 'B': 'length', 'C': 'length', 'D': 'stop'}),
 ('mixed', 0.8): ({'A': [461, 19, 351, 481, 465, 377, 294, 378, 309, 210, 378],
                   'B': [359, 282, 50, 167, 489, 394, 163, 370, 285, 343, 201, 406, 110, 451, 209,
                         105, 99, 424],
                   'C': [48, 245, 494, 375, 195, 123, 36, 97, 451, 96, 294, 114, 369, 283, 374, 280,
                         311, 323, 479, 285, 311, 91, 143, 83, 508],
                   'D': [264, 285, 345, 472, 405, 140]},
                  {'A': 'length', 'B': 'length', 'C': 'length', 'D': 'stop'}),
 ('mixed-late', 0.0): ({'A': [499, 364, 339, 18, 183, 8, 391, 339, 80, 466, 159],
                        'B': [343, 244, 303, 280, 115, 489, 268, 511, 255, 476, 3, 464, 16, 22, 357,
                              327, 445, 327],
                        'C': [141, 21, 280, 66, 257, 65, 324, 253, 102, 280, 64, 280, 292, 246, 219,
                              334, 395, 479, 56, 6, 416, 218, 342, 429, 187],
                        'D': [92, 97, 489, 174, 292, 259],
                        'E': [190, 219, 389, 364, 462, 279, 462, 81, 213]},
                       {'A': 'length', 'B': 'length', 'C': 'length', 'D': 'stop', 'E': 'length'}),
 ('reused', 0.0): ({'A': [257, 113, 458, 111, 249, 458, 111],
                    'B': [437, 491, 437, 91, 177, 119, 48, 48, 177, 292, 451, 177, 257, 294, 177,
                          153, 336, 81, 147, 247, 365, 199, 451, 48, 26, 219, 71],
                    'C': [257, 113, 458, 111, 249, 458, 111, 378, 278, 223, 104, 499, 466, 18, 414,
                          267, 507, 370, 156, 378, 195, 122, 104, 464],
                    'E': [304, 188, 464, 407, 382, 327, 163, 150, 326, 374, 315, 487, 315]},
                   {'A': 'stop', 'B': 'length', 'C': 'length', 'E': 'length'})}


def _serve(plan, t, **cfg):
    kw = _PLANS[plan](t, _STOP.get((plan, t)))
    return _by_hand(**{**kw, **cfg})


def _counter(eng, name, labels=None):
    return eng.telemetry.registry.get_sample_value(name, labels)


def _free_blocks(eng):
    return getattr(eng.allocator, "reusable_blocks", eng.allocator.free_blocks)


@pytest.mark.parametrize(
    "plan, t", _STOP_AT,
    ids=[f"{plan}-{'greedy' if t == 0 else 'sampled'}" for plan, t in _STOP_AT])
def test_a_chunk_in_flight_serves_the_parents_tokens(plan, t):
    toks, why, eng = _serve(plan, t)
    assert (toks, why) == _PARENT_SERVED[plan, t]
    assert _free_blocks(eng) == eng.n_blocks - 1    # every block came back
    # Back to back, every chunk but the first went out with one unread.
    alone, ahead = (_counter(eng, "jetstream:decode_chunks_total",
                             {"dispatch": d}) for d in ("alone", "ahead"))
    assert alone == 1 and ahead >= 2
    # A whole lane-chunk is thrown away for each request that a stop token
    # or an abort ended with the next chunk in flight, and for no other.
    assert _counter(eng, "jetstream:decode_lanes_discarded_total") == sum(
        reason in ("stop", "abort") for reason in why.values()) == 1
    if plan == "reused":
        # C found A's prompt blocks as A's prefill left them, and decoded
        # through blocks that A's overshoot had scribbled on.
        assert toks["C"][:len(toks["A"])] == toks["A"]
        assert _counter(eng, "jetstream:prefix_cached_tokens_total") == 32


# ---- a slot that the chunk in flight vacates is refilled ahead -------------

@pytest.fixture(params=["tiny", "tiny-hybrid"])
def family(request):
    """The llama family, and the hybrid one (a state pool row a slot) in
    float32 under a name of its own."""
    if request.param == "tiny":
        yield "tiny"
        return
    import dataclasses

    from llm_d_inference_scheduler_tpu.models import configs

    name = "tiny-hybrid-f32-refill"
    configs._REGISTRY[name] = dataclasses.replace(
        configs.get_config("tiny-hybrid"), name=name, dtype="float32")
    yield name
    del configs._REGISTRY[name]


def _queue(stop=None):
    """Four requests for two lanes: A and B decode first, C and D wait. The
    ends fall in the middle of a chunk (A, C), on its last step (B) and on
    its first (D)."""
    return [_req("A", _prompt(5, 9), 11, 0.0, stop),
            _req("B", _prompt(7, 20), 17, 0.0),
            _req("C", _prompt(11, 27), 14, 0.0),
            _req("D", _prompt(13, 14), 6, 0.0)]


class _Watch:
    """What a run did, step by step, seen from outside the loop."""

    def __init__(self):
        self.chunks = []      # (requests waiting before the step, its lanes)
        self.running = []     # every value the gauge was set to
        self.freed = []       # every block list handed back
        self.held = set()     # the blocks out now
        self.twice = []       # blocks freed while not out
        self.retired = []     # retired requests left over between steps

    def __call__(self, eng, step):
        if step is None:
            gauge, dispatch = eng.telemetry.running.set, eng._dispatch_chunk
            alloc, free = eng.allocator.alloc, eng.allocator.free
            eng.telemetry.running.set = lambda v: (self.running.append(v),
                                                   gauge(v))[1]

            def allocated(n):
                blocks = alloc(n)
                self.held |= set(blocks)
                return blocks

            def freed(blocks):
                self.freed.append(list(blocks))
                self.twice += [b for b in blocks if b not in self.held]
                self.held -= set(blocks)
                free(blocks)

            eng.allocator.alloc, eng.allocator.free = allocated, freed

            def dispatched():
                chunk = dispatch()
                if chunk is not None:
                    self.chunks.append(
                        (self.waiting, [s.req.request_id
                                        for _, s in chunk.lanes]))
                return chunk

            eng._dispatch_chunk = dispatched
            return
        self.waiting = len(eng._waiting)
        self.retired += eng._retired


def _alone(requests, family):
    """Each request's tokens when it is served alone, on one engine."""
    toks, why, _ = _by_hand(
        requests, model=family, max_batch=2,
        submit_at={r.request_id: 40 * n for n, r in enumerate(requests)})
    assert set(why.values()) <= {"length"}
    return toks


def _refills(eng):
    return tuple(_counter(eng, "jetstream:slot_refills_total", {"when": w})
                 for w in ("ahead", "after"))


_QUEUED = {}


def _queued(family):
    """_queue() on two lanes, served once a family for the tests below."""
    if family not in _QUEUED:
        watch = _Watch()
        toks, why, eng = _by_hand(_queue(), model=family, max_batch=2,
                                  watch=watch)
        _QUEUED[family] = toks, why, eng, watch
    return _QUEUED[family]


def test_refilled_ahead_every_request_gets_the_tokens_it_gets_alone(family):
    toks, why, _, _ = _queued(family)
    assert toks == _alone(_queue(), family)
    assert [len(toks[r.request_id]) for r in _queue()] == [11, 17, 14, 6]


def test_refilled_ahead_the_predecessor_is_served_to_its_end(family):
    toks, why, eng, watch = _queued(family)
    # Its last tokens came through the chunk it was retired in, and its end.
    assert why == dict.fromkeys("ABCD", "length")
    # Every request's blocks came back once, and nothing else did.
    assert len(watch.freed) == 4 and not watch.twice and not watch.held
    assert _free_blocks(eng) == eng.n_blocks - 1
    assert max(watch.running) == 2 and watch.running[-1] == 0
    assert not watch.retired and not eng._retired


def test_refilled_ahead_no_lane_is_empty_while_a_request_waits(family):
    _, _, eng, watch = _queued(family)
    # C took A's slot and D took B's with their last chunks unread.
    assert _refills(eng) == (2, 2)
    waited = [lanes for waiting, lanes in watch.chunks[1:] if waiting]
    assert waited and all(len(lanes) == 2 for lanes in waited)
    # A's and C's lanes are one slot's, in consecutive chunks.
    ids = [lanes for _, lanes in watch.chunks]
    last_a = max(n for n, lanes in enumerate(ids) if "A" in lanes)
    assert "C" in ids[last_a + 1] and "A" not in ids[last_a + 1]
    assert _counter(eng, "jetstream:decode_lanes_discarded_total") == 0


def test_refill_waits_for_the_booking_where_the_pool_is_too_small(family):
    """Four usable blocks, two a request: the predecessor's come back at its
    booking, and until then the head of the queue does not fit."""
    watch = _Watch()
    toks, why, eng = _by_hand(_queue(), model=family, max_batch=2,
                              hbm_kv_blocks=5, watch=watch)
    assert _refills(eng) == (0, 4)
    assert why == dict.fromkeys("ABCD", "length")
    assert toks == _queued(family)[0]
    assert len(watch.freed) == 4 and not watch.twice and not watch.held
    assert _free_blocks(eng) == eng.n_blocks - 1


@pytest.mark.parametrize("who, at", [
    # A is aborted before the step that would have found it vacating: C takes
    # the empty slot, and D takes B's ahead.
    ("A", 3),
    # C is aborted in A's slot, a step after it was refilled ahead, in a chunk
    # in flight: D takes the empty slot.
    ("C", 4)])
def test_refill_ahead_with_an_abort_leaks_nothing(family, who, at):
    watch = _Watch()
    toks, why, eng = _by_hand(_queue(), model=family, max_batch=2,
                              abort_at={who: at}, watch=watch)
    assert why == {**dict.fromkeys("ABCD", "length"), who: "abort"}
    served = _queued(family)[0]
    assert all(toks[r] == served[r] for r in "ABCD" if r != who)
    assert toks[who] == served[who][:len(toks[who])]
    assert _refills(eng) == (1, 3)
    assert len(watch.freed) == 4 and not watch.twice and not watch.held
    assert _free_blocks(eng) == eng.n_blocks - 1
    assert not watch.retired and not eng._retired


def test_refill_ahead_then_a_failed_step_ends_the_retired_request_too(family):
    """The loop fails between C's prefill into A's slot and the booking of
    A's last chunk: A is in no slot, and is aborted with the rest."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    async def body():
        eng = TpuEngine(EngineConfig(
            model=family, backend="tpu", max_model_len=128, max_batch=2,
            decode_chunk=_CHUNK, seed=11, kv_events_port=0),
            params=_tiny_f32() if family == "tiny" else None)
        outs = {r.request_id: eng.submit(r) for r in _queue()}
        read = eng._read_tokens
        eng._read_tokens = lambda toks: (
            read(toks) if not eng._retired else 1 / 0)
        for _ in range(40):
            try:
                eng._step()
            except ZeroDivisionError:
                eng._abort_all("engine loop failure")   # as _run does
                break
        else:
            raise AssertionError("no request was retired in 40 steps")
        await asyncio.sleep(0)
        ends = {}
        for rid, out in outs.items():
            while not out.empty():
                ev = out.get_nowait()
                if ev.finish_reason is not None:
                    ends[rid] = ev.finish_reason.value
        return ends, eng

    ends, eng = asyncio.run(body())
    assert ends == dict.fromkeys("ABCD", "abort")
    assert not eng._retired and not any(eng.slots)
    assert _free_blocks(eng) == eng.n_blocks - 1


def test_a_lane_that_ends_on_a_stop_token_is_not_refilled_ahead(family):
    """A's sixth token is its stop token: nothing says so before it is read,
    and C takes the slot after the booking, as it always did."""
    served = _queued(family)[0]["A"]
    at = next(n for n in range(5, 11) if served[n] not in served[:n])
    requests = _queue(stop=served[at])[:3]
    requests[1].max_tokens = 40                 # B outlasts both
    toks, why, eng = _by_hand(requests, model=family, max_batch=2)
    assert why == {"A": "stop", "B": "length", "C": "length"}
    assert toks["A"] == served[:at] and toks["C"] == _queued(family)[0]["C"]
    assert _refills(eng) == (0, 3)
    assert _counter(eng, "jetstream:decode_lanes_discarded_total") == 1
    assert _free_blocks(eng) == eng.n_blocks - 1


# ---- a prompt is written in windows, and a short prompt has one ------------
# One function dispatches a text prompt's programs (_write_prefill_window),
# at admission where the prompt fits a window (ISSUE 56). _ONE_WINDOW holds
# what the parent commit (ab98553: a path of its own for a whole prompt)
# dispatched and served for the same requests under the same driver.

_SHORT_PROMPT = _prompt(41, 41)     # two whole blocks of 16, and nine tokens


class _Programs:
    """The prefill programs a run dispatched, each as (op, its operands'
    shapes by name, the loop's phase), and every event it emitted."""

    def __init__(self, fail=None):
        self.ops, self.events, self.failed = [], [], []
        self.fail = fail    # the prompt whose program the device refuses

    def __call__(self, eng, step):
        if step is not None:
            return
        real_op, real_phase = eng._exec_op, eng._phase
        real_emit, real_step = eng._emit, eng._step
        now = []

        @contextlib.contextmanager
        def phase(name):
            now.append(name)
            try:
                with real_phase(name):
                    yield
            finally:
                now.pop()

        def exec_op(op, args):
            if "prefill" in op[0]:
                n = len(self.fail or ())
                if n and args["tokens"][0, :n].tolist() == self.fail:
                    raise RuntimeError("the device refuses this program")
                self.ops.append((op, {k: np.shape(v) for k, v in args.items()},
                                 now[-1]))
            return real_op(op, args)

        def emit(slot, ev):
            self.events.append(ev)
            real_emit(slot, ev)

        def step_():
            try:
                real_step()
            except RuntimeError:    # the failed request is cleaned up: go on
                self.failed.append([r.request_id for r, *_ in eng._waiting])

        eng._exec_op, eng._phase = exec_op, phase
        eng._emit, eng._step = emit, step_


def _shapes(width, **more):
    return {"tokens": (1, width), "row": (1, 8), "slots": (1,),
            "temps": (1,), "top_k": (1,), "top_p": (1,), **more}


_SERVED = [177, 41, 256, 244, 278, 415]
_IMAGE_SERVED = [305, 69, 395, 145, 341]
_ONE_WINDOW = {
    False: ([(("prefill", 64), _shapes(64, seq_len=(1,)), "admit")],
            {"P": _SERVED}),
    True: ([(("prefill", 64), _shapes(64, seq_len=(1,)), "admit"),
            (("prefix_prefill", 16, 2),
             _shapes(16, suffix_len=(1,), prefix_len=(1,), prior=(1, 2)),
             "admit")],
           {"P": _SERVED, "Q": _SERVED}),
}


@pytest.mark.parametrize("prefill_chunk", [0, 64])
@pytest.mark.parametrize("hit", [False, True], ids=["cold", "prefix-hit"])
def test_a_prompt_of_one_window_is_the_parents_whole_prompt(hit, prefill_chunk):
    """A prompt that fits a window (or has no window size) goes out as the
    parent's whole prompt did: the same program with the same operands in
    the same phase, the same tokens; after a prefix hit, the continuation."""
    reqs = [_req("P", _SHORT_PROMPT, 6, 0.0)]
    if hit:
        reqs.append(_req("Q", _SHORT_PROMPT, 6, 0.0))
    seen = _Programs()
    toks, why, eng = _by_hand(reqs, submit_at={"Q": 20}, watch=seen,
                              prefill_chunk=prefill_chunk)
    assert (seen.ops, toks) == _ONE_WINDOW[hit]
    assert _counter(eng, "jetstream:prompt_tokens_total") == 41 + 9 * hit
    assert _counter(eng, "jetstream:prefix_cached_tokens_total") == 32 * hit
    assert _free_blocks(eng) == eng.n_blocks - 1


def test_duplicates_admitted_in_one_step_share_the_firsts_blocks():
    """Four requests with one prompt, admitted in one step: the first is
    written, and each of the others finds its two whole blocks committed
    and continues from them; the same tokens, the hit reported."""
    seen = _Programs()
    toks, why, eng = _by_hand(
        [_req(f"d{i}", _SHORT_PROMPT, 4, 0.0) for i in range(4)], watch=seen)
    assert [(op[0], phase) for op, _, phase in seen.ops] == [
        ("prefill", "admit")] + [("prefix_prefill", "admit")] * 3
    assert all(t == toks["d0"] and len(t) == 4 for t in toks.values())
    assert [ev.cached_tokens for ev in seen.events if ev.is_first] == [
        0, 32, 32, 32]
    assert _counter(eng, "jetstream:prefix_cached_tokens_total") == 96
    assert _free_blocks(eng) == eng.n_blocks - 1


@pytest.mark.parametrize("prefill_chunk", [0, 16])
def test_an_image_prompt_is_one_window_with_two_more_operands(prefill_chunk):
    """Three encoder vectors over the placeholders at positions 1 to 3 of a
    34-token prompt: one program, whole, whatever the window size (the
    splice targets absolute positions), as the parent dispatched it."""
    vectors = np.random.default_rng(0).standard_normal((3, 128)).tolist()
    req = _req("M", [1, 5, 5, 5] + list(range(10, 40)), 5, 0.0)
    req.mm_embeds, req.mm_positions = vectors, [1, 2, 3]
    seen = _Programs()
    toks, why, eng = _by_hand([req], watch=seen, prefill_chunk=prefill_chunk)
    assert seen.ops == [(("mm_prefill", 64, 4), _shapes(
        64, seq_len=(1,), mm_pad=(1, 4, 128), pos_pad=(1, 4)), "admit")]
    assert toks == {"M": _IMAGE_SERVED}
    assert _counter(eng, "jetstream:prompt_tokens_total") == 34
    assert _free_blocks(eng) == eng.n_blocks - 1


@pytest.mark.parametrize("into", ["an empty slot", "a vacating slot"])
def test_a_dispatch_that_fails_ends_its_own_request_and_no_other(into):
    """The device refuses one request's program. That client gets ABORT and
    the blocks come back; the requests behind it still wait, and are served
    as they are when nothing fails; a predecessor that the failed request
    had retired (refilled ahead) is served to its end by its chunk."""
    if into == "an empty slot":
        reqs = [_req("A", _prompt(5, 9), 7, 0.0),
                _req("B", _prompt(7, 20), 5, 0.0),
                _req("C", _prompt(11, 27), 6, 0.0)]
        kw, bad, behind = dict(max_batch=4), "B", ["C"]
        served = _by_hand(reqs, **kw)[0]
    else:
        reqs, kw, bad, behind = _queue(), dict(max_batch=2), "C", ["D"]
        served = _queued("tiny")[0]
    watch = _Watch()
    seen = _Programs(fail=next(r.prompt_token_ids for r in reqs
                               if r.request_id == bad))

    def both(eng, step):
        watch(eng, step)
        seen(eng, step)

    toks, why, eng = _by_hand(reqs, watch=both, **kw)
    assert seen.failed == [behind]
    assert why == {**dict.fromkeys(served, "length"), bad: "abort"}
    assert toks == {**served, bad: []}
    assert len(watch.freed) == len(reqs) and not watch.twice and not watch.held
    assert _free_blocks(eng) == eng.n_blocks - 1
    assert not eng._retired and not any(eng.slots)
    if into == "a vacating slot":
        assert _refills(eng) == (1, 3)      # C ahead; D after, in its place


def test_engine_config_has_no_prefill_batch():
    import dataclasses

    names = [f.name for f in dataclasses.fields(EngineConfig)]
    assert "prefill_batch" not in names and len(names) == 44


def test_the_server_refuses_prefill_batch_as_any_unknown_flag(capsys):
    from llm_d_inference_scheduler_tpu.engine import server

    with pytest.raises(SystemExit) as refused:
        server.main(["--backend", "sim", "--prefill-batch", "2"])
    assert refused.value.code == 2
    assert "unrecognized arguments: --prefill-batch" in capsys.readouterr().err


def test_a_prompts_programs_are_dispatched_from_one_place_each():
    """Outside the warm-up, one dispatch of each prefill program in
    TpuEngine's source, and one commit of a finished prompt's blocks."""
    import inspect
    import re

    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    source = inspect.getsource(TpuEngine)
    for apart in (TpuEngine._warmup, TpuEngine._import_into_slot):
        source = source.replace(inspect.getsource(apart), "")
    sites = collections.Counter(re.findall(
        r'_device_call\(\s*\("(\w*prefill)"', source))
    assert sites == {"prefill": 1, "prefix_prefill": 1, "mm_prefill": 1}
    assert source.count(".commit_hashes(") == 1
    assert not re.search(
        "_flush_admissions|_run_batched_prefill|_try_prepare_batch_entry"
        "|pending_idx|prefill_batch", source)


# ---- the next chunk is held back for an arrival ----------------------------

_PREFILL_S, _CHUNK_S = 0.040, 0.200
_SHORT, _HALF = (_CHUNK // SHORT_CHUNK_DIVS[n] for n in ("quarter", "half"))


@contextlib.contextmanager
def _time_limit(seconds):
    """Fail the test, and do not hang the run, if the block is not done."""
    def late(*_):
        raise AssertionError(f"not done in {seconds} s")

    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


class _Device:
    """A clock the test owns and a device with an in-order queue: a prefill
    takes 40 ms and a chunk 50 ms a step (200 at its full length), each
    after whatever was dispatched before it; reading an op's tokens moves
    the clock to that op's end; the host costs nothing. The sleep of a held
    chunk (TpuEngine._await_work) is the
    test's too: hold number n (from 0) finds in ``script[n]`` what happens in
    it, a list of (seconds into the hold, something to call); after the last
    of them, or with none, the clock moves to the deadline. ``log`` holds
    every device call and every hold as (kind, the clock, what): a decode
    chunk's request ids, a prefill's slots, a hold's deadline; ``lengths``
    every chunk's steps; ``reads`` the clock after every read."""

    def __init__(self, eng):
        self.script = {}
        self.now, self.free, self.ends = 0.0, 0.0, collections.deque()
        self.log, self.reads, self.n_holds, self._due = [], [], 0, None
        self.lengths = []
        self.real_wait = eng._await_work
        real_op, real_read, real_chunk = (eng._exec_op, eng._read_tokens,
                                          eng._dispatch_chunk)

        def exec_op(op, args):
            if op[0] in ("prefill", "prefix_prefill", "decode"):
                took = _PREFILL_S
                if op[0] == "decode":
                    took = _CHUNK_S * args["steps"] / _CHUNK
                    self.lengths.append(args["steps"])
                self.free = max(self.free, self.now) + took
                self.ends.append(self.free)
                self.log.append((op[0], self.now,
                                 [int(i) for i in args["slots"]]))
            return real_op(op, args)

        def read_tokens(toks):
            self.now = max(self.now, self.ends.popleft())
            self.reads.append(self.now)
            return real_read(toks)

        def dispatch_chunk():
            chunk = real_chunk()
            if chunk is not None:
                kind, at, _ = self.log[-1]
                self.log[-1] = (kind, at, [s.req.request_id
                                           for _, s in chunk.lanes])
            return chunk

        eng._clock = lambda: self.now
        eng._exec_op, eng._read_tokens = exec_op, read_tokens
        eng._dispatch_chunk, eng._await_work = dispatch_chunk, self.await_work

    def await_work(self, until):
        if self._due is None:                   # a hold begins
            self.log.append(("hold", self.now, until))
            self._due = [(self.now + after, call) for after, call
                         in self.script.get(self.n_holds, ())]
            self.n_holds += 1
        if self._due:
            at, call = self._due.pop(0)
            assert self.now <= at < until
            self.now = at
            return bool(call())
        self.now, self._due = until, None
        return False

    def end_hold(self):
        """For a script: nothing more happens in this hold."""
        self._due = None

    def kinds(self):
        return [kind for kind, *_ in self.log]


def _held(requests, *, script=None, at_step=None, hold=True, steps=200,
          setup=None, **cfg):
    """Serve by hand on a _Device. ``at_step``: request id -> the step before
    which it is submitted. ``script``: hold number -> [(seconds into it, the
    id of a request to submit then, or something to call with both)].
    ``hold`` False: the deadline is always now, no hold is ever taken.
    ``setup(eng, dev)`` runs once before the first step. Returns (tokens by
    id, finish reason by id, engine, device)."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    at_step = at_step or {}
    by_id = {r.request_id: r for r in requests}
    cfg.setdefault("max_batch", 4)

    async def body():
        eng = TpuEngine(EngineConfig(
            model="tiny", backend="tpu", max_model_len=128,
            decode_chunk=_CHUNK, seed=11, kv_events_port=0, **cfg),
            params=_tiny_f32())
        outs, toks, why = {}, {}, {}

        def submit(rid):
            outs[rid], toks[rid] = eng.submit(by_id[rid]), []
            return True

        dev = _Device(eng)
        dev.script = {
            n: [(after, functools.partial(submit, what)
                 if isinstance(what, str) else functools.partial(what, eng, dev))
                for after, what in events]
            for n, events in (script or {}).items()}
        if not hold:
            eng._hold_until = lambda: None
        if setup is not None:
            setup(eng, dev)
        for step in range(steps):
            for rid, at in at_step.items():
                if at == step:
                    submit(rid)
            dev.log.append(("step", dev.now, step))
            eng._step()
            await asyncio.sleep(0)
            for rid, out in outs.items():
                while not out.empty():
                    ev = out.get_nowait()
                    if ev.token_id is not None:
                        toks[rid].append(ev.token_id)
                    if ev.finish_reason is not None:
                        why[rid] = ev.finish_reason.value
            if len(why) == len(requests) and eng._inflight is None:
                break
        return toks, why, eng, dev

    with _time_limit(120):
        return asyncio.run(body())


def _admissions(eng):
    return tuple(_counter(eng, "jetstream:admissions_total", {"at": at})
                 for at in ("hold", "step"))


def _long(rid="A", n=9, max_tokens=100):
    return _req(rid, _prompt(5, n), max_tokens, 0.0)


def test_an_arrival_in_a_hold_is_prefilled_ahead_of_the_next_chunk():
    """A decodes alone on four lanes; B arrives 30 ms into the first hold and
    C 20 ms after it. Each prefill goes out at the arrival, behind the chunk
    that runs and ahead of the held one, which goes out at the deadline with
    both as lanes; their first tokens are read a prefill after that chunk's
    end and not a chunk later."""
    reqs = [_long(), _req("B", _prompt(7, 20), 9, 0.0),
            _req("C", _prompt(11, 12), 5, 0.0)]
    toks, why, eng, dev = _held(reqs, at_step={"A": 0},
                                script={0: [(0.030, "B"), (0.050, "C")]})
    assert why == dict.fromkeys("ABC", "length")
    assert [len(toks[r]) for r in "ABC"] == [100, 9, 5]
    first = dev.kinds().index("hold")
    (_, began, until), *after = dev.log[first:]
    assert [(kind, round(at - began, 6)) for kind, at, _ in after[:3]] == [
        ("prefill", 0.030), ("prefill", 0.050),
        ("decode", round(until - began, 6))]
    assert after[2][2] == ["A", "B", "C"]
    assert _admissions(eng) == (2, 1)
    assert _refills(eng) == (0, 3)
    # The step's three reads: the running chunk at its end, then B's first
    # token a prefill later and C's behind it, the held chunk behind both.
    ends = until + HOLD_MARGIN_S
    assert [at for at in dev.reads if at > began][:3] == pytest.approx(
        [ends, ends + _PREFILL_S, ends + 2 * _PREFILL_S])


def _lengths(eng):
    return tuple(_counter(eng, "jetstream:decode_chunk_lengths_total",
                          {"length": n}) for n in ("short", "full"))


def _fractions(eng):
    return tuple(_counter(eng, "jetstream:decode_chunk_fractions_total",
                          {"fraction": n})
                 for n in ("quarter", "half", "full"))


def test_with_no_arrival_a_held_chunk_goes_out_at_the_deadline():
    """Every chunk from the first hold on is dispatched HOLD_MARGIN_S before
    the end of the chunk ahead of it, never later and (the periods being all
    alike) not sooner; the device is never without work, and every chunk
    still goes out with the one before it unread. From the first hold on the
    chunks are short (three slots are open and nobody waits), and the first
    of them is reckoned pro rata from the full ones."""
    toks, why, eng, dev = _held([_long(max_tokens=61)], at_step={"A": 0})
    assert why == {"A": "length"} and len(toks["A"]) == 61
    chunks = [(at, ids) for kind, at, ids in dev.log if kind == "decode"]
    holds = [(at, until) for kind, at, until in dev.log if kind == "hold"]
    # Three chunks go out as they always did, until the second is read and
    # the shape is timed: 12 of the 60 tokens; the last hold is behind the
    # last chunk, which nothing follows.
    assert dev.lengths == [_CHUNK] * 3 + [_SHORT] * (48 // _SHORT)
    assert _lengths(eng) == (48 // _SHORT, 3)
    assert len(holds) == len(chunks) - 3 + 1
    # The first chunk ends a prefill and a chunk after time 0, the rest back
    # to back.
    ends = [_PREFILL_S + _CHUNK_S * sum(dev.lengths[:n + 1]) / _CHUNK
            for n in range(len(chunks))]
    assert [until for _, until in holds] == pytest.approx(
        [end - HOLD_MARGIN_S for end in ends[2:]], abs=1e-9)
    assert [at for at, _ in chunks[3:]] == [until for _, until in holds[:-1]]
    assert dev.free == pytest.approx(ends[-1])          # no gap on the device
    assert _counter(eng, "jetstream:decode_chunks_total",
                    {"dispatch": "alone"}) == 1
    assert _admissions(eng) == (0, 1)
    # The sleep is decode_wait's: with a host that costs nothing, all of the
    # loop's time is.
    get = eng.telemetry.registry.get_sample_value
    assert get("jetstream:engine_loop_seconds_total",
               {"phase": "decode_wait"}) == pytest.approx(dev.now)


def _no_hold_waits():
    """B needs seven blocks where two are left: it stays the head of the
    queue until A's come back, and the chunks go out as they always did."""
    return dict(requests=[_long(), _long("B")], at_step={"A": 0, "B": 8},
                hbm_kv_blocks=10), "waiting", range(8, 26)


def _no_hold_busy():
    """Two lanes, both decoding: no slot for an arrival until A's last chunk
    is in flight (a slot that is known to vacate is open)."""
    return dict(requests=[_long(), _long("B")], at_step={"A": 0, "B": 8},
                max_batch=2), "open", range(8, 20)


def _no_hold_prefilling():
    """B's prompt is written in six windows of 16, one a step."""
    return dict(requests=[_long(), _req("B", _prompt(7, 90), 8, 0.0)],
                at_step={"A": 0, "B": 8}, prefill_chunk=16), "prefilling", range(8, 13)


def _no_hold_idle():
    """A has ended and its last chunk is booked: the steps after it, and B's
    own first, find nothing in flight, though the shape is timed and every
    slot is free (so B's first chunk, held back by nothing, is short)."""
    return dict(requests=[_long(max_tokens=21), _long("B", max_tokens=5)],
                at_step={"A": 0, "B": 16}), "inflight", range(12, 17)


def _no_hold_untimed():
    """The first chunk of a shape is not timed and the second is read in
    the third's step: nothing to reckon an end from until then."""
    return dict(requests=[_long()], at_step={"A": 0}), "timed", range(1, 3)


def _slow_booking(seconds):
    def setup(eng, dev):
        def book(lanes, sampled, real=eng._book_chunk):
            dev.now += seconds
            return real(lanes, sampled)

        eng._book_chunk = book
    return setup


def _quarter_for_the_host():
    """Booking a chunk costs the host 35 ms: with the 10 ms it must have to
    spare that fits a quarter's 50, so every chunk from the fourth on is a
    quarter as long, as with a host that costs nothing; but 30 ms before a
    quarter ends the loop is still booking, so none of them is held back
    (the hold asks its own margin of the chunk: 20 ms)."""
    return dict(requests=[_long()], at_step={"A": 0},
                also=_slow_booking(0.035)), "host", range(3, 24)


def _half_for_the_host():
    """Booking a chunk costs the host 45 ms: with the 10 ms to spare that is
    more than a quarter's 50 and fits a half's 100, so every chunk from the
    fourth on is half as long and none is full; the holds are taken as ever
    (35 ms are left of a half's 100 before its deadline)."""
    return dict(requests=[_long()], at_step={"A": 0},
                also=_slow_booking(0.045)), "host", range(3, 24)


def _full_for_the_host():
    """Booking a chunk costs the host 95 ms: with the 10 ms to spare that is
    more than a half's 100, so every chunk is full; the holds are taken as
    ever (85 ms are left of a full chunk's 200 before its deadline)."""
    return dict(requests=[_long()], at_step={"A": 0},
                also=_slow_booking(0.095)), "host", range(3, 24)


for _case in (_no_hold_waits, _no_hold_busy, _no_hold_prefilling,
              _no_hold_idle, _no_hold_untimed):
    _case.length = _CHUNK
_quarter_for_the_host.length = _SHORT
_half_for_the_host.length, _full_for_the_host.length = _HALF, _CHUNK


@pytest.mark.parametrize("case", [
    _no_hold_waits, _no_hold_busy, _no_hold_prefilling, _no_hold_idle,
    _no_hold_untimed, _quarter_for_the_host, _half_for_the_host,
    _full_for_the_host], ids=lambda case: case.__name__[1:])
def test_a_chunk_is_held_back_and_cut_short_only_where_an_arrival_fits(case):
    """In the steps named, the one thing named stands in the way: of the
    hold (the chunk goes out at once) and of the short length (it is full),
    or of the one it concerns alone: nothing in flight stops a hold and no
    length, the host's own work a length and no hold. Over the whole run a
    chunk is held in exactly the steps in which nothing stands in the hold's
    way, and short in exactly those in which nothing stands in its length's:
    an arrival could be placed at once, the chunk's shape has been timed (at
    any length), and the loop's work a chunk fits inside a short one: the
    shortest it fits in, a quarter, or a half where a quarter is too short
    for it. Both counters say the same as the device's log."""
    plan, blocker, quiet = case()
    also = plan.pop("also", None)
    holds, lengths = {}, {}

    def room(eng):
        return dict(
            waiting=not eng._waiting, open=any(eng._open_slots()),
            prefilling=not any(s is not None and s.prefilling
                               for s in eng.slots))

    def watch(eng, dev):
        def step():
            return [what for kind, _, what in dev.log if kind == "step"][-1]

        def hold_until(real=eng._hold_until):
            chunk = eng._inflight
            reckoned = chunk and eng._chunk_time(chunk.shape, chunk.steps)
            state = dict(
                inflight=chunk is not None, **room(eng),
                timed=any(shape == chunk.shape for shape, _ in eng._chunk_times)
                if chunk else bool(eng._chunk_times),
                # (The deadline still lies ahead: the host is done in time.)
                early=not reckoned or dev.now < max(
                    chunk.t0, eng._last_readback, eng._first_tokens_read)
                + reckoned - HOLD_MARGIN_S)
            until = real()
            holds.setdefault(step(), (state, until))
            return until

        def chunk_steps(shape, real=eng._chunk_steps):
            # (The shortest length the host's work and the margin fit in.)
            fits = next(n for n in (_SHORT, _HALF, _CHUNK) if n == _CHUNK
                        or max(eng._host_work, default=0) + KEEP_UP_S
                        <= _CHUNK_S * n / _CHUNK)
            state = dict(
                **room(eng),
                timed=any(sh == shape for sh, _ in eng._chunk_times),
                host=fits < _CHUNK)
            lengths[step()] = (state, real(shape), fits)
            return lengths[step()][1]

        eng._hold_until, eng._chunk_steps = hold_until, chunk_steps
        if also is not None:
            also(eng, dev)

    toks, why, eng, dev = _held(setup=watch, **plan)
    assert set(why.values()) == {"length"}
    for step, (state, until) in holds.items():
        assert (until is not None) == all(state.values()), (step, state)
    for step, (state, steps, fits) in lengths.items():
        assert steps == (fits if all(state.values()) else _CHUNK), (
            step, state)
    assert sorted(dev.lengths) == sorted(n for _, n, _ in lengths.values())
    assert _lengths(eng) == (len(dev.lengths) - dev.lengths.count(_CHUNK),
                             dev.lengths.count(_CHUNK))
    assert _fractions(eng) == tuple(
        dev.lengths.count(n) for n in (_SHORT, _HALF, _CHUNK))
    for step in quiet:
        for state, *_ in filter(None, (holds.get(step), lengths.get(step))):
            if blocker in state:    # (the blocker of one of the two alone)
                # (A host that fits a short length stands in nothing's way.)
                assert [k for k, ok in state.items() if not ok] == (
                    [blocker] if case.length == _CHUNK else []), (step, state)
    if blocker == "host":
        # (Three full chunks until the shape is timed and the host measured.)
        assert set(dev.lengths[3:]) == {case.length}
        # A chunk is held back wherever the host is done HOLD_MARGIN_S
        # before the one in flight ends, whatever the length it then gets:
        # not at a quarter whose 50 ms the host's 35 nearly fill.
        # (The first quarter is in flight from the quiet steps' third on.)
        assert all((holds[step][1] is not None)
                   == (case is not _quarter_for_the_host)
                   == holds[step][0]["early"] for step in quiet[2:])
    elif blocker == "inflight":
        assert lengths[quiet[-1]][1] == _SHORT      # B's first chunk
    else:
        assert holds[quiet[0] - 1][1] is not None or blocker == "timed"
        assert all(lengths[step][1] == _CHUNK for step in quiet)
    assert any(until is not None for step, (_, until) in holds.items()
               if step > quiet[-1]) or case is _quarter_for_the_host
    assert _SHORT in [n for step, (_, n, _) in lengths.items()
                      if step > quiet[-1]] or blocker == "host"


def _stands_queue(eng):
    eng._waiting.append((_long("Q"), None, None))


def _stands_windows(eng):
    eng.slots[1] = types.SimpleNamespace(prefilling=True)


def _stands_pp(eng):
    eng.pp_mesh = object()


def _stands_untimed(eng):
    eng._chunk_times.clear()


def _stands_unmeasured(eng):
    eng._host_work.clear()


def _quarter_timed(eng):
    """The quarter has a period of its own, shorter than pro rata."""
    eng._chunk_times[("4x8", _SHORT)] = collections.deque([0.045])


@pytest.mark.parametrize("host, also, steps", [
    (0.0, None, _SHORT), (0.040, None, _SHORT), (0.041, None, _HALF),
    (0.090, None, _HALF), (0.091, None, _CHUNK), (0.500, None, _CHUNK),
    (0.030, _quarter_timed, _SHORT), (0.040, _quarter_timed, _HALF),
    (0.0, _stands_queue, _CHUNK), (0.0, _stands_windows, _CHUNK),
    (0.0, _stands_pp, _CHUNK), (0.0, _stands_untimed, _CHUNK),
    (0.0, _stands_unmeasured, _CHUNK)],
    ids=lambda v: getattr(v, "__name__", str(v)).lstrip("_"))
def test_a_short_chunk_is_the_shortest_the_hosts_work_fits_in(host, also,
                                                             steps):
    """The shape's full chunk was timed at 200 ms, so a quarter is reckoned
    at 50 and a half at 100 until either has a period of its own: the chunk
    is the shortest of quarter, half and full that the host's mean work a
    period plus KEEP_UP_S fits in, a host too slow for a quarter falls to
    a half and not to a whole chunk, and with a queue, windows being
    written, a pipeline's program, a shape never timed or a loop never
    measured it is full whatever the host costs."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    eng = TpuEngine(EngineConfig(
        model="tiny", backend="tpu", max_model_len=128, decode_chunk=_CHUNK,
        seed=11, kv_events_port=0, max_batch=4), params=_tiny_f32())
    eng._chunk_times[("4x8", _CHUNK)] = collections.deque([_CHUNK_S, 0.3])
    eng._host_work.extend([host - 0.01, host + 0.01, host])
    if also is not None:
        also(eng)
    assert eng._chunk_steps("4x8") == steps
    # Another shape's periods say nothing of this one's.
    assert eng._chunk_steps("2x8") == _CHUNK


def test_a_request_is_served_whole_through_short_and_full_chunks():
    """Two lanes. A (19 tokens, 18 of them decoded: no multiple of either
    length once three full chunks are gone) decodes alone, through full
    chunks until its shape is timed and short ones from then on; B and C
    arrive together: B takes the empty slot, C waits, so the chunk that
    follows is full, and A's end lies inside it; C is prefilled ahead into
    A's slot and is a lane of the very next chunk, which A is not. Every
    request gets exactly its tokens, the ones it gets alone."""
    reqs = [_req("A", _prompt(5, 9), 19, 0.0), _req("B", _prompt(7, 20), 14, 0.0),
            _req("C", _prompt(11, 27), 7, 0.0)]
    toks, why, eng, dev = _held(
        reqs, at_step={"A": 0, "B": 5, "C": 5}, max_batch=2)
    assert why == dict.fromkeys("ABC", "length")
    assert [len(toks[r.request_id]) for r in reqs] == [19, 14, 7]
    alone, _, _ = _by_hand(reqs, max_batch=2, submit_at={
        r.request_id: 40 * n for n, r in enumerate(reqs)})
    assert toks == alone
    ids = [ids for kind, _, ids in dev.log if kind == "decode"]
    assert [n for lanes, n in zip(ids, dev.lengths) if lanes == ["A"]] == [
        _CHUNK] * 3 + [_SHORT] * 2
    last_a = max(n for n, lanes in enumerate(ids) if "A" in lanes)
    assert ids[last_a] == ["A", "B"] and dev.lengths[last_a] == _CHUNK
    assert "C" in ids[last_a + 1] and "A" not in ids[last_a + 1]
    assert _refills(eng) == (1, 2)
    short, full = _lengths(eng)
    assert short >= 1 and full >= 1 and short + full == len(ids)
    assert _counter(eng, "jetstream:decode_lanes_discarded_total") == 0
    assert _free_blocks(eng) == eng.n_blocks - 1


@pytest.mark.parametrize("what", ["abort", "stop"])
def test_an_abort_or_a_stop_ends_a_hold_at_once(what):
    """The loop's own wait (not the test's), asked to sleep to a deadline
    30 ms away (a short chunk's 50 less the margin) on a clock that stands
    still: an abort or the stop notifies
    _cond and the wait is over; the chunk goes out there and then, not at
    the deadline, and the step goes on to its end."""
    woke = []

    def interrupt(eng, dev):
        if what == "abort":
            eng.abort("A")
        else:
            with eng._cond:
                eng._stop = True
                eng._cond.notify()
        until = [e for e in dev.log if e[0] == "hold"][-1][2]
        t0 = time.monotonic()
        woke.append(dev.real_wait(until))
        woke.append(time.monotonic() - t0)
        dev.end_hold()
        return woke[0]

    toks, why, eng, dev = _held(
        [_long()], at_step={"A": 0}, script={1: [(0.010, interrupt)]}, steps=7)
    assert woke[0] is True and woke[1] < 0.05
    second = [n for n, kind in enumerate(dev.kinds()) if kind == "hold"][1]
    (_, began, until), (kind, at, ids), *rest = dev.log[second:]
    assert kind == "decode" and at == pytest.approx(began + 0.010)
    assert at < until - 0.015 and ids == ["A"]
    if what == "abort":
        # Processed at the top of the next step, as an abort that arrives
        # during a readback always was.
        assert why == {"A": "abort"} and rest[0][0] == "step"


def _mixed_arrivals():
    return [_req("A", _prompt(5, 9), 41, 0.0), _req("B", _prompt(7, 20), 18, 0.0),
            _req("C", _prompt(11, 33), 25, 0.0), _req("D", _prompt(13, 14), 9, 0.0),
            _req("E", _prompt(17, 12), 13, 0.0), _req("F", _prompt(19, 25), 6, 0.0)]


def test_greedy_streams_are_the_same_held_and_never_held():
    """Six requests on four lanes: B and C arrive inside holds, D at the top
    of a step, E and F while every lane is taken (they are refilled ahead).
    The same requests with no hold ever taken (the deadline always now), all
    arriving at the top of steps: every stream is the same, token for token;
    so is each request alone."""
    reqs = _mixed_arrivals()
    at_step = {"A": 0, "D": 7, "E": 8, "F": 8}
    toks, why, eng, dev = _held(
        reqs, at_step=at_step, script={0: [(0.020, "B")], 2: [(0.015, "C")]})
    assert why == dict.fromkeys("ABCDEF", "length")
    assert [len(toks[r.request_id]) for r in reqs] == [41, 18, 25, 9, 13, 6]
    assert _admissions(eng) == (2, 4)
    plain, why_plain, eng_plain, dev_plain = _held(
        reqs, at_step={**at_step, "B": 4, "C": 6}, hold=False)
    assert "hold" not in dev_plain.kinds() and "hold" in dev.kinds()
    assert _admissions(eng_plain) == (0, 6)
    # Both were served through chunks of both lengths, not the same ones.
    assert {_SHORT, _CHUNK} == set(dev.lengths) == set(dev_plain.lengths)
    assert dev.lengths != dev_plain.lengths
    assert toks == plain and why == why_plain
    alone, _, _ = _by_hand(reqs, submit_at={
        r.request_id: 40 * n for n, r in enumerate(reqs)})
    assert toks == alone
    for e in (eng, eng_plain):
        assert _free_blocks(e) == e.n_blocks - 1


def test_admissions_are_counted_once_a_request():
    """Held or not, placed at once or after a wait for blocks, refilled ahead
    or into an empty slot: one count a request, where slot_refills counts
    it; a request that is refused counts in neither."""
    reqs = _mixed_arrivals() + [_req("X", _prompt(23, 30), 400, 0.0)]
    toks, why, eng, dev = _held(
        reqs, at_step={"A": 0, "D": 7, "E": 8, "F": 8, "X": 9},
        script={0: [(0.020, "B")], 2: [(0.015, "C")]}, hbm_kv_blocks=8)
    assert why == {**dict.fromkeys("ABCDEF", "length"), "X": "abort"}
    hold, step = _admissions(eng)
    assert hold >= 1 and hold + step == 6 == sum(_refills(eng))


# ---------- the experts a decode step read, counted on the device ----------

def _decode_expert_counts(interpret):
    """Two requests on a chip's share of a double-layer model (8 of its 16
    experts, widths the kernel tiles), stepped by hand with every decode
    chunk the device was handed written down: (jetstream:moe_decode_experts_
    total by label, [(lanes, steps) a chunk], the engine)."""
    import dataclasses

    from llm_d_inference_scheduler_tpu.models import configs

    name = "tiny-longcat-share"
    configs._REGISTRY[name] = dataclasses.replace(
        configs.get_config("tiny-longcat"), name=name, d_model=128,
        moe_d_ff=128, experts_held=8, experts_first=4)
    chunks = []

    def watch(eng, step):
        if step is not None:
            return
        real = eng._exec_op

        def exec_op(op, args):
            if op[0] == "decode":
                chunks.append((len(args["slots"]), args["steps"]))
            return real(op, args)

        eng._exec_op = exec_op

    try:
        *_, eng = _by_hand(
            [_req("A", _prompt(7, 20), 9, 0.0), _req("B", _prompt(5, 33), 6,
                                                     0.0)],
            submit_at={"B": 2}, model=name, watch=watch, warmup=False,
            max_batch=2, pallas_attention=False, pallas_interpret=interpret)
    finally:
        del configs._REGISTRY[name]
    eng.telemetry.book_pair_counts()
    return ({read: _counter(eng, "jetstream:moe_decode_experts_total",
                            {"read": read}) for read in ("yes", "no")},
            chunks, eng)


def test_the_experts_a_decode_step_read_add_up_to_held_x_layers_x_steps():
    """Where decode programs read the chosen experts alone, every (held
    expert, expert layer, step) is booked once, read or not: at least one an
    expert layer a step (the tile that stands in where none is chosen), and
    fewer than all of them."""
    counts, chunks, eng = _decode_expert_counts(interpret=True)
    assert eng.bound.model_for(2).moe_impl == "chosen_interpret"
    steps = sum(n for _, n in chunks)
    assert chunks and {lanes for lanes, _ in chunks} == {2}
    assert counts["yes"] + counts["no"] == 8 * 2 * steps
    assert 2 * steps <= counts["yes"] < 8 * 2 * steps
    # The host's count of rows by form reads these programs as dense.
    assert _counter(eng, "jetstream:moe_ffn_tokens_total",
                    {"form": "dense"}) >= 2 * steps


def test_an_engine_whose_decode_programs_stay_dense_has_no_such_series():
    counts, chunks, eng = _decode_expert_counts(interpret=False)
    assert eng.bound.model_for(2).moe_impl == "dense" and chunks
    assert counts == {"yes": None, "no": None}
    assert "moe_decode_experts_total{" not in eng.telemetry.render().decode()
    assert _counter(eng, "jetstream:moe_routed_pairs_total",
                    {"held": "yes"}) > 0


if __name__ == "__main__":
    import pprint

    _STOP.clear()
    for case, at in _STOP_AT.items():
        if at is not None:
            stream = _serve(*case)[0][at[0]]
            assert stream[at[1]] not in stream[:at[1]], (case, stream)
            _STOP[case] = stream[at[1]]
    print("_STOP = " + pprint.pformat(_STOP, compact=True, width=79))
    print("_PARENT_SERVED = " + pprint.pformat(
        {case: _serve(*case)[:2] for case in _STOP_AT},
        compact=True, width=79))
