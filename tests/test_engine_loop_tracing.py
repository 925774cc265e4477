"""The engine describes its own loop and its own requests (ISSUE 24): loop
phases as a counter and as host spans, a request's wait split at the
admission pop, a compile counter that counts programs, step programs with
stable names, and the profiler's control on the engine server. CPU, `tiny`;
every wait is bounded."""

import asyncio
import glob
import os

import httpx
import jax
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
from llm_d_inference_scheduler_tpu.engine.server import EngineServer
from llm_d_inference_scheduler_tpu.engine.telemetry import (
    LOOP_PHASES,
    XLA_BUILDS,
    EngineTelemetry,
)

PORT = 18940


def run(coro):
    return asyncio.run(coro)


def _cfg(backend, port, **kw):
    return EngineConfig(model="tiny", backend=backend, port=port, max_batch=2,
                        max_model_len=128, decode_chunk=4, **kw)


def _hist(telemetry, name):
    get = telemetry.registry.get_sample_value
    return get(name + "_count"), get(name + "_sum")


def _loop_seconds(telemetry):
    return {p: telemetry.registry.get_sample_value(
        "jetstream:engine_loop_seconds_total", {"phase": p}) for p in LOOP_PHASES}


# ---------- a request's wait, split where it happens ----------

@pytest.mark.parametrize("stream", [False, True], ids=["unary", "streamed"])
@pytest.mark.parametrize("backend", ["tpu", "sim"])
def test_wait_histograms_once_per_request_and_sum_to_ttft(backend, stream):
    """Five requests on two lanes, so some queue: each is observed once in
    both histograms whether or not it streams, and queue wait + admit to
    first token is the TTFT less the hop from construction to submit()."""
    port = PORT + 2 * (backend == "sim") + stream
    n = 5

    async def body():
        server = EngineServer(_cfg(backend, port))
        await server.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                answers = await asyncio.gather(*[c.post(
                    f"http://127.0.0.1:{port}/v1/completions",
                    json={"model": "tiny", "prompt": f"hello {i} " * 3,
                          "max_tokens": 6, "ignore_eos": True, "stream": stream})
                    for i in range(n)])
            assert [a.status_code for a in answers] == [200] * n
            return server.engine.telemetry
        finally:
            await server.stop()

    telemetry = run(body())
    q_n, q_sum = _hist(telemetry, "jetstream:queue_wait_seconds")
    a_n, a_sum = _hist(telemetry, "jetstream:admit_to_first_token_seconds")
    t_n, t_sum = _hist(telemetry, "jetstream:time_to_first_token_seconds")
    assert q_n == a_n == t_n == n
    assert q_sum > 0 and a_sum > 0
    assert q_sum + a_sum <= t_sum + 1e-6
    assert t_sum - (q_sum + a_sum) < 0.05 * n    # the submit hop, per request


# ---------- the loop's phases ----------

_STEP_ORDER = ("housekeeping", "admit", "advance_prefills", "decode_prepare",
               "decode_dispatch", "decode_wait", "decode_book", "decode_wait",
               "finalize_prefills")


def test_loop_seconds_cover_the_loops_wall_time_and_decode_wait_needs_a_chunk():
    """The loop's accounting on a clock the test owns: it moves by one second
    for every device op dispatched and by five for every blocking read of
    the device, and stands still otherwise. _step() is called by hand, so
    the phases are those of known steps: they never overlap and leave no
    gap (their sum is the clock), a chunk is dispatched before the one in
    flight is read and booked, a wait inside _finalize_prefills is
    decode_wait's and not finalize_prefills', and decode_wait moves only in
    a step that began with a chunk in flight or dispatched a prefill."""
    async def body():
        eng = TpuEngine(_cfg("tpu", 0, kv_events_port=0))
        now, ops, reads, order = [0.0], [], [0], []

        def exec_op(op, args, real=eng._exec_op):
            now[0] += 1.0
            ops.append(op[0])
            return real(op, args)

        def read_tokens(toks, real=eng._read_tokens):
            now[0] += 5.0
            reads[0] += 1
            return real(toks)

        def phase(name, real=eng._phase):
            order[-1].append(name)
            return real(name)

        eng._clock = lambda: now[0]
        eng._exec_op, eng._read_tokens, eng._phase = exec_op, read_tokens, phase

        def step():
            order.append([])
            before = _loop_seconds(eng.telemetry)["decode_wait"]
            chunk, prefills = eng._inflight is not None, ops.count("prefill")
            eng._step()
            moved = _loop_seconds(eng.telemetry)["decode_wait"] - before
            return moved, (chunk, ops.count("prefill") > prefills)

        for _ in range(3):                   # nothing to do: nothing moves
            assert step() == (0.0, (False, False))
        assert now[0] == 0.0 and sum(_loop_seconds(eng.telemetry).values()) == 0.0

        outs = [eng.submit(EngineRequest(
            request_id=rid, prompt_token_ids=[1, 7, 8, 9 + i], max_tokens=10,
            ignore_eos=True)) for i, rid in enumerate("ab")]
        moved = [step() for _ in range(6)]
        await asyncio.sleep(0)
        assert all(out.qsize() == 11 for out in outs)     # 10 tokens, the end

        # Step 1 had nothing to read but waited for the first tokens, in
        # _finalize_prefills, with the first chunk queued behind the
        # prefills; steps 2 to 4 read the chunk before the one they
        # dispatched; the rest found nothing on the device.
        assert [m for m, _ in moved] == [10.0, 5.0, 5.0, 5.0, 0.0, 0.0]
        assert all((m > 0) == (chunk or first) for m, (chunk, first) in moved)
        seconds = _loop_seconds(eng.telemetry)
        assert seconds["decode_wait"] == 5.0 * reads[0] == 25.0
        assert seconds["admit"] == ops.count("prefill") >= 1
        assert seconds["decode_dispatch"] == ops.count("decode") == 3
        assert {p for p, v in seconds.items() if v} == {
            "admit", "decode_dispatch", "decode_wait"}
        assert sum(seconds.values()) == now[0]            # no gap, no overlap
        for names in order:
            at = -1
            for name in names:       # each step: a subsequence of the order
                at = _STEP_ORDER.index(name, at + 1)
        head = list(_STEP_ORDER[:5])
        assert order[3] == head + ["decode_wait", "finalize_prefills"]
        assert order[4] == head + ["decode_wait", "decode_book"]

    run(body())


# ---------- a compile counter that counts programs ----------

def test_xla_builds_move_on_a_fresh_jit_and_not_on_its_second_call():
    telemetry = EngineTelemetry(block_size=16, num_blocks=4)
    telemetry.watch_xla_builds()

    def builds():
        get = telemetry.registry.get_sample_value
        return sum(get("jetstream:xla_builds_total", {"kind": k})
                   for k in ("compiled", "cache_loaded"))

    x = jax.numpy.arange(7.0)
    jax.block_until_ready(x)
    fresh = jax.jit(lambda v: v * 3.0 + 1.0)
    n0, s0 = builds(), XLA_BUILDS.seconds
    jax.block_until_ready(fresh(x))
    n1 = builds()
    assert n1 == n0 + 1 and XLA_BUILDS.seconds > s0
    jax.block_until_ready(fresh(x))
    assert builds() == n1
    assert telemetry.registry.get_sample_value(
        "jetstream:xla_build_seconds_total") == pytest.approx(XLA_BUILDS.seconds)


def test_a_simulator_shows_no_build_counter():
    telemetry = EngineTelemetry(block_size=16, num_blocks=4)
    assert b"jetstream:xla_builds_total{" not in telemetry.render()
    assert b'engine_loop_seconds_total{phase="decode_book"}' in telemetry.render()


# ---------- stable program names ----------

@pytest.fixture(scope="module")
def engine():
    return TpuEngine(_cfg("tpu", 0))        # never started: lowering only


def _sampling(eng, n=1):
    return (eng._next_key(True), np.zeros((n,), np.float32),
            np.zeros((n,), np.int32), np.ones((n,), np.float32))


def _lowered(which, eng):
    row = np.zeros((1, eng.max_blocks_per_seq), np.int32)
    one = np.ones((1,), np.int32)
    if which == "prefill":
        return eng._prefill_fn(32).lower(
            eng.params, np.zeros((1, 32), np.int32), one, eng.k_pages,
            eng.v_pages, row, *_sampling(eng))
    if which == "prefix_prefill":
        return eng._prefix_prefill_fn(16, 2).lower(
            eng.params, np.zeros((1, 16), np.int32), one, one, eng.k_pages,
            eng.v_pages, row, np.zeros((1, 2), np.int32), *_sampling(eng))
    if which == "kv_import":
        return eng._jit_import.lower(
            eng.k_pages, eng.v_pages, np.zeros((2,), np.int32),
            eng.k_pages[:, :2], eng.v_pages[:, :2])
    return eng._embed_fn_for(64).lower(
        eng.params, np.zeros((1, 64), np.int32), one)


@pytest.mark.parametrize("which, name", [
    ("prefill", "jit_prefill_b32"),
    ("prefix_prefill", "jit_prefix_prefill_s16_p2"),
    ("kv_import", "jit_kv_import"),
    ("embed", "jit_embed_b64")])
def test_step_programs_carry_their_names(engine, which, name):
    assert f"@{name} " in _lowered(which, engine).as_text()


def test_the_decode_chunk_keeps_its_name(engine):
    args = (engine.params, np.zeros((2,), np.int32), np.zeros((2,), np.int32),
            engine.k_pages, engine.v_pages,
            np.zeros((2, engine.max_blocks_per_seq), np.int32),
            *_sampling(engine, 2))
    assert "@jit__decode_chunk_impl " in \
        engine._jit_decode_chunk.lower(*args).as_text()


# ---------- the profiler's control ----------

async def _complete(c, port, i, max_tokens=8):
    return await c.post(f"http://127.0.0.1:{port}/v1/completions",
                        json={"model": "tiny", "prompt": f"trace me {i}",
                              "max_tokens": max_tokens, "ignore_eos": True,
                              "stream": i % 2 == 0})


def test_profile_endpoints_are_404_without_profile_dir():
    port = PORT + 5

    async def body():
        server = EngineServer(_cfg("sim", port))
        await server.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                return [(await c.post(f"http://127.0.0.1:{port}/debug/profile/{v}")
                         ).status_code for v in ("start", "stop")]
        finally:
            await server.stop()

    assert run(body()) == [404, 404]


def _engine_span_names(profile_dir):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    names = set()
    threads = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            found = {ev.name for ev in line.events if ev.name.startswith("engine.")}
            if found:
                threads.add((plane.name, line.name))
                names |= found
    return names, threads


def test_profile_writes_engine_spans_and_a_second_start_is_409(tmp_path):
    port = PORT + 6

    async def body():
        server = EngineServer(_cfg("tpu", port, profile_dir=str(tmp_path)))
        await server.start()
        try:
            url = f"http://127.0.0.1:{port}/debug/profile/"
            async with httpx.AsyncClient(timeout=120) as c:
                assert (await c.post(url + "stop")).status_code == 409   # not on
                assert (await _complete(c, port, 0)).status_code == 200  # compiles
                started = await c.post(url + "start")
                assert started.status_code == 200 and started.json()["tracing"]
                assert (await c.post(url + "start")).status_code == 409
                answers = await asyncio.gather(*[_complete(c, port, i)
                                                 for i in range(1, 4)])
                assert [a.status_code for a in answers] == [200] * 3
                stopped = await c.post(url + "stop")
                assert stopped.status_code == 200
                assert set(stopped.json()) == {"traced_s", "start_trace_s",
                                               "stop_trace_s"}
                assert stopped.json()["traced_s"] > 0
                assert (await c.post(url + "stop")).status_code == 409
        finally:
            await server.stop()

    run(body())
    names, threads = _engine_span_names(str(tmp_path))
    assert {"engine.admit", "engine.decode_dispatch", "engine.decode_wait",
            "engine.decode_book", "engine.finalize_prefills"} <= names
    assert names <= {f"engine.{p}" for p in LOOP_PHASES}
    assert len(threads) == 1                 # the engine's thread alone


def test_a_request_served_while_the_profiler_stops_completes(tmp_path):
    port = PORT + 7

    async def body():
        server = EngineServer(_cfg("tpu", port, profile_dir=str(tmp_path)))
        await server.start()
        try:
            url = f"http://127.0.0.1:{port}/debug/profile/"
            async with httpx.AsyncClient(timeout=120) as c:
                assert (await _complete(c, port, 1)).status_code == 200
                assert (await c.post(url + "start")).status_code == 200
                assert (await _complete(c, port, 2)).status_code == 200
                stop = asyncio.create_task(c.post(url + "stop"))
                during = await asyncio.gather(*[_complete(c, port, i, 24)
                                                for i in range(3, 6)])
                stopped = await asyncio.wait_for(stop, timeout=120)
                assert stopped.status_code == 200
                assert [a.status_code for a in during] == [200] * 3
                assert all(a.headers.get("content-type", "").startswith(
                    "text/event-stream") or a.json()["usage"]["completion_tokens"] == 24
                    for a in during)
        finally:
            await server.stop()

    run(body())
