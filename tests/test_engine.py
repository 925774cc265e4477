"""Engine tests: continuous batching core, HTTP surface, telemetry, P/D handoff."""

import asyncio
import json

import httpx
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
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


def test_decode_ctx_buckets_token_parity():
    """Pow2 context-bucketed block tables (decode_ctx_buckets) must be
    token-identical to full-width tables, across mixed request lengths and
    a width drop when the long request finishes first."""
    import jax
    import jax.numpy as jnp

    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
    from llm_d_inference_scheduler_tpu.models import llama
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    params = llama.init_params(get_config("tiny"), jax.random.key(11),
                               dtype=jnp.float32)

    async def serve(ctx_buckets: bool):
        eng = TpuEngine(EngineConfig(
            model="tiny", backend="tpu", max_batch=4, max_model_len=128,
            decode_chunk=4, seed=11, kv_events_port=0,
            enable_prefix_caching=False, decode_ctx_buckets=ctx_buckets),
            params=params)
        await eng.start()
        try:
            async def one(rid, n_prompt, n_gen):
                req = EngineRequest(
                    request_id=rid,
                    prompt_token_ids=[1] + [(i * 3) % 400 + 5
                                            for i in range(n_prompt - 1)],
                    max_tokens=n_gen, temperature=0.0, ignore_eos=True)
                out = eng.submit(req)
                toks = []
                while True:
                    ev = await out.get()
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.finish_reason is not None:
                        return toks

            # short (3 blocks) + long (7 blocks) concurrently: W=8 while both
            # live, drops to 4 after the long one finishes first.
            long_t, short_t = await asyncio.gather(
                one("long", 100, 6), one("short", 40, 24))
            return long_t, short_t
        finally:
            await eng.stop()

    bucketed = asyncio.run(serve(True))
    full = asyncio.run(serve(False))
    assert bucketed == full
    assert len(bucketed[0]) == 6 and len(bucketed[1]) == 24


def test_batched_prefill_token_parity():
    """prefill_batch > 1: same-bucket plain prompts admitted together run
    as ONE [K, S] fused prefill (padded to K) — greedy tokens must match
    the per-prompt path exactly, including the prefix-cache-hit rerun
    (hits route back to the O(prefix) single path)."""
    import asyncio

    from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    prompts = [[1] + [(i * 13 + j * 7) % 400 + 3 for j in range(40)]
               for i in range(6)]
    base = dict(model="tiny", backend="tpu", max_batch=8, max_model_len=64,
                decode_chunk=4, kv_events_port=0, seed=5)

    async def serve(cfg, tag, rounds=1):
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            async def one(rid, prompt):
                out = eng.submit(EngineRequest(
                    request_id=rid, prompt_token_ids=list(prompt),
                    max_tokens=5, temperature=0.0, ignore_eos=True))
                toks = []
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=120)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.finish_reason is not None:
                        return toks

            out = []
            for r in range(rounds):
                out.append(await asyncio.gather(
                    *[one(f"{tag}{r}-{i}", p) for i, p in enumerate(prompts)]))
            return out
        finally:
            await eng.stop()

    single = asyncio.run(serve(EngineConfig(**base), "s"))[0]
    cold, warm = asyncio.run(serve(
        EngineConfig(**base, prefill_batch=4), "b", rounds=2))
    assert cold == single
    assert warm == single  # prefix-cache hits take the single path


def test_batched_prefill_in_group_duplicates_share_prefix():
    """K identical prompts admitted in ONE group: the first prefills in the
    batch, the duplicates reroute to the prefix path AFTER the batch commits
    its hashes — same tokens, and the duplicates report cached tokens."""
    import asyncio

    from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    prompt = [1] + [(j * 11) % 400 + 3 for j in range(40)]

    async def body():
        eng = TpuEngine(EngineConfig(model="tiny", backend="tpu", max_batch=8,
                                     max_model_len=64, decode_chunk=4,
                                     kv_events_port=0, seed=5,
                                     prefill_batch=4))
        await eng.start()
        try:
            async def one(rid):
                out = eng.submit(EngineRequest(
                    request_id=rid, prompt_token_ids=list(prompt),
                    max_tokens=4, temperature=0.0, ignore_eos=True))
                toks, cached = [], 0
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=120)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                        cached = max(cached, ev.cached_tokens or 0)
                    if ev.finish_reason is not None:
                        return toks, cached

            results = await asyncio.gather(*[one(f"d{i}") for i in range(4)])
            toks = [t for t, _ in results]
            cached = [c for _, c in results]
            assert all(t == toks[0] for t in toks)
            # At least the rerouted duplicates hit the freshly-committed
            # prefix blocks (2 complete 16-token blocks of the 41-token
            # prompt).
            assert sum(1 for c in cached if c >= 32) >= 3
        finally:
            await eng.stop()

    asyncio.run(body())


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

        def counted_write(idx):
            steps[-1].append(idx)
            write(idx)

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


_CASES = [(*case, {}) for case in _STOP_AT] + [
    # Tables narrowed to the live context: the overshoot of a lane two chunks
    # past its end clamps to its row's last entry, its own block or the trash.
    ("reused", 0.0, {"decode_ctx_buckets": True})]


@pytest.mark.parametrize(
    "plan, t, cfg", _CASES,
    ids=[f"{plan}-{'greedy' if t == 0 else 'sampled'}{'-narrowed' * bool(cfg)}"
         for plan, t, cfg in _CASES])
def test_a_chunk_in_flight_serves_the_parents_tokens(plan, t, cfg):
    toks, why, eng = _serve(plan, t, **cfg)
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
