"""The engine describes its own loop and its own requests (ISSUE 24): loop
phases as a counter and as host spans, a request's wait split at the
admission pop, a compile counter that counts programs, step programs with
stable names, and the profiler's control on the engine server. CPU, `tiny`;
every wait is bounded."""

import asyncio
import collections
import glob
import json
import os
import time

import httpx
import jax
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.core import HOLD_MARGIN_S, TpuEngine
from llm_d_inference_scheduler_tpu.engine.server import EngineServer
from llm_d_inference_scheduler_tpu.engine.telemetry import (
    LOOP_PHASES,
    STALL_WHERE,
    XLA_BUILDS,
    EngineTelemetry,
)
from llm_d_inference_scheduler_tpu.router.metrics import PERIOD_BUCKETS

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

_STEP_ORDER = ("housekeeping", "admit", "advance_prefills", "decode_wait",
               "decode_prepare", "decode_dispatch", "decode_wait",
               "decode_book", "decode_wait", "finalize_prefills")


def test_loop_seconds_cover_the_loops_wall_time_and_decode_wait_needs_a_chunk():
    """The loop's accounting on a clock the test owns: it moves by one second
    for every device op dispatched and by five for every blocking read of
    the device, and stands still otherwise. _step() is called by hand, so
    the phases are those of known steps: they never overlap and leave no
    gap (their sum is the clock), a chunk is dispatched before the one in
    flight is read and booked, a wait inside _finalize_prefills is
    decode_wait's and not finalize_prefills', and decode_wait moves only in
    a step that began with a chunk in flight or dispatched a prefill. Where
    the next chunk is held back for an arrival (TpuEngine._hold_for_arrival: a slot
    is free, nobody waits, and a chunk of the shape in flight has been timed)
    the sleep is decode_wait's too, ahead of decode_prepare; here it costs
    nothing, and nothing arrives in it."""
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
        eng._await_work = lambda until: bool(order[-1].append("hold"))

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
        # The third chunk's step is the first that finds a chunk in flight
        # whose shape has been timed (the second chunk's period, read in the
        # step before): the one hold, to 20 ms before the end it reckons.
        assert [names.count("hold") for names in order[3:]] == [0, 0, 0, 1, 0, 0]
        assert order[6][:5] == list(_STEP_ORDER[:4]) + ["hold"]
        for names in order:
            at = -1
            for name in names:       # each step: a subsequence of the order
                if name != "hold":
                    at = _STEP_ORDER.index(name, at + 1)
        head = [*_STEP_ORDER[:3], *_STEP_ORDER[4:6]]
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
            *_sampling(engine, 2), np.int32(1))
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


# ---------- where a stream stopped (ISSUE 36) ----------

def _value(registry, name, **labels):
    return registry.get_sample_value(name, labels or None)


def _stall_seconds(eng):
    return {w: _value(eng.telemetry.registry, "jetstream:loop_stall_seconds_total",
                      where=w) for w in STALL_WHERE}


def _chunks(eng):
    return _hist(eng.telemetry, "jetstream:decode_step_duration_seconds")[0]


def _long_engine(**kw):
    """Two lanes, 127 chunks of 4 a request: never started, stepped by hand."""
    return TpuEngine(EngineConfig(
        model="tiny", backend="tpu", port=0, max_batch=kw.pop("max_batch", 2),
        max_model_len=512, decode_chunk=4, kv_events_port=0, **kw))


def _submit(eng, rid, max_tokens=500):
    return eng.submit(EngineRequest(
        request_id=rid, prompt_token_ids=[1, 7, 8, 9], max_tokens=max_tokens,
        ignore_eos=True))


@pytest.mark.parametrize("where, other", [("device_wait", "host"),
                                          ("host", "device_wait")])
def test_a_stop_of_800_ms_is_one_stall_in_the_place_it_happened(where, other):
    """Forty quiet chunks, then 0.8 s once: in the read of a chunk's tokens
    (the device, or whatever the loop is blocked on), or in _book_chunk (the
    engine thread alone). The real clock; only the provoked step's own
    movement is looked at, so a hiccup of the machine elsewhere is not read."""
    async def body():
        eng = _long_engine()
        _submit(eng, "a")
        while _chunks(eng) < 40:
            eng._step()
        slowed = {"device_wait": "_read_tokens", "host": "_book_chunk"}[where]
        real = getattr(eng, slowed)
        slept = []

        def slow(*args):
            if not slept:
                t0 = time.monotonic()
                time.sleep(0.8)
                slept.append(time.monotonic() - t0)   # 0.8 s and the wake-up
            return real(*args)

        setattr(eng, slowed, slow)
        before, n = _stall_seconds(eng), len(eng.stalls.ring)
        bucket = lambda le: _value(
            eng.telemetry.registry,
            "jetstream:decode_step_duration_seconds_bucket", le=le)
        under = bucket("0.65"), bucket("1.5")
        # The sleep in the read ends a period; the one in the booking falls
        # into the period the NEXT readback ends.
        for _ in range(2):
            eng._step()
        moved = {w: v - before[w] for w, v in _stall_seconds(eng).items()}
        assert len(eng.stalls.ring) == n + 1
        stall = eng.stalls.ring[-1]
        assert moved[where] == pytest.approx(slept[0] - stall["median_s"], abs=0.05)
        assert moved[other] == pytest.approx(0.0, abs=0.05)
        if where == "host":
            assert moved["device_wait"] == 0.0      # the device was long done
            assert stall["phases_s"]["decode_book"] >= 0.8
        else:
            assert stall["phases_s"]["decode_wait"] >= 0.8
        assert stall["excess_s"] == pytest.approx(moved)
        assert stall["period_s"] == pytest.approx(slept[0], abs=0.05)
        assert sum(stall["phases_s"].values()) == pytest.approx(
            stall["period_s"], abs=1e-3)
        assert set(stall["phases_s"]) == set(LOOP_PHASES) | {"none"}
        assert (stall["lanes"], stall["batch"], stall["prefills"]) == (1, 2, 0)
        assert abs(stall["unix"] - time.time()) < 5
        assert abs(stall["loop_clock_s"] - time.monotonic()) < 5
        # Two chunks landed: a quiet one, and the one in le="1" or "1.5".
        assert (bucket("0.65"), bucket("1.5")) == (under[0] + 1, under[1] + 2)
        return eng

    eng = run(body())
    server = EngineServer(eng.cfg, engine=eng)

    async def served():
        return json.loads((await server.stalls(None)).body)

    page = run(served())
    assert page["count"] == len(eng.stalls.ring)
    assert page["stalls"][0] == eng.stalls.ring[-1]


class _ScriptedDevice:
    """A clock the test owns and a device with a queue: an op takes its cost
    AFTER whatever was dispatched before it, and a read of an op's tokens
    moves the clock to that op's end, if it is not there yet. The host costs
    nothing. Reads come in the order of dispatch (a chunk, the prefills
    behind it, the chunk behind those), so a queue of ends is enough."""

    def __init__(self, eng, cost, build=lambda op, args: 0.0):
        self.now, self.free, self.ends = 0.0, 0.0, collections.deque()
        self.cost, self.ops = cost, []
        self.chunks = []       # (dispatched, read) of every decode chunk
        self._t0 = collections.deque()
        real_op, real_read = eng._exec_op, eng._read_tokens

        def exec_op(op, args):
            self.now += build(op, args)      # the host, building a program
            took = self.cost(op[0], len(self.ops))
            self.ops.append(op[0])
            if op[0] in ("prefill", "decode"):
                self.free = max(self.free, self.now) + took
                self.ends.append((op[0], self.free))
                if op[0] == "decode":
                    self._t0.append(self.now)
            return real_op(op, args)

        def read_tokens(toks):
            kind, end = self.ends.popleft()
            self.now = max(self.now, end)
            if kind == "decode":
                self.chunks.append((self._t0.popleft(), self.now))
            return real_read(toks)

        def await_work(until):   # a held chunk's sleep: nothing arrives
            self.now = max(self.now, until)
            return False

        eng._clock = lambda: self.now
        eng._exec_op, eng._read_tokens = exec_op, read_tokens
        eng._await_work = await_work

    def parents_arithmetic(self):
        """What PR 35's _land_chunk observed: each chunk but the first (a
        shape's first call is not timed) from the later of its dispatch and
        the readback before it to its own readback."""
        last, spans = 0.0, []
        for t0, read in self.chunks:
            spans.append(read - max(t0, last))
            last = read
        return spans[1:]


def test_quiet_chunks_are_no_stall_and_sum_and_count_are_the_parents():
    """Sixty-four chunks whose device time wanders between 100 and 180 ms:
    nothing is a stall, /debug/stalls stays empty, and the histogram's sum
    and count are what the parent's arithmetic gives for the same clock: the
    new buckets changed nothing that decode_chunk_ms reads."""
    async def body():
        eng = _long_engine()
        dev = _ScriptedDevice(eng, lambda kind, i: 0.1 + 0.02 * (i % 5))
        _submit(eng, "a")
        while len(dev.chunks) < 65:
            eng._step()
        spans = dev.parents_arithmetic()
        count, total = _hist(eng.telemetry, "jetstream:decode_step_duration_seconds")
        assert count == len(spans) == 64
        assert total == pytest.approx(sum(spans), rel=1e-12)
        assert _stall_seconds(eng) == {"device_wait": 0.0, "host": 0.0}
        assert not eng.stalls.ring
        get = eng.telemetry.registry.get_sample_value
        bounds = [s.labels["le"] for m in eng.telemetry.registry.collect()
                  if m.name == "jetstream:decode_step_duration_seconds"
                  for s in m.samples if s.name.endswith("_bucket")]
        assert bounds == [str(float(b)) for b in PERIOD_BUCKETS] + ["+Inf"]
        assert get("jetstream:decode_step_duration_seconds_bucket",
                   {"le": "0.2"}) == 64

    run(body())


def test_three_prefills_ahead_of_a_chunk_are_no_stall_and_a_long_read_is():
    """longdoc-batch's worst honest step: a chunk of 133 ms with three
    prefill windows of 62 ms queued ahead of it is 319 ms, under twice the
    median. The same device taking 2 s over one chunk is a stall, all of it
    device_wait."""
    async def body():
        eng = _long_engine(max_batch=4)
        slow = []
        dev = _ScriptedDevice(eng, lambda kind, i: (
            0.062 if kind == "prefill" else 2.133 if slow and slow.pop() else 0.133))
        _submit(eng, "a")
        while len(dev.chunks) < 12:
            eng._step()
        prefills = dev.ops.count("prefill")
        for rid in "bcd":
            _submit(eng, rid)
        while len(dev.chunks) < 16:
            eng._step()
        assert dev.ops.count("prefill") == prefills + 3
        spans = dev.parents_arithmetic()
        assert max(spans) == pytest.approx(0.133 + 3 * 0.062)
        assert _stall_seconds(eng) == {"device_wait": 0.0, "host": 0.0}
        assert not eng.stalls.ring

        slow.append(True)
        n = len(dev.chunks)
        while len(dev.chunks) < n + 3:
            eng._step()
        assert _stall_seconds(eng) == {"device_wait": pytest.approx(2.0),
                                       "host": 0.0}
        (stall,) = eng.stalls.ring
        assert stall["period_s"] == pytest.approx(2.133)
        assert stall["median_s"] == pytest.approx(0.133)
        assert stall["phases_s"]["decode_wait"] == pytest.approx(2.133)
        assert (stall["lanes"], stall["batch"]) == (4, 4)

    run(body())


def test_a_period_that_holds_a_first_call_of_a_shape_is_no_stall():
    """Two requests join a lone lane: the chunk for four rows is built while
    the two-row chunk in flight waits to be read, 16 s here. That chunk's
    period holds the build and is observed as the parent observed it, but
    it is nobody's stall, as the first call itself is not timed at all."""
    async def body():
        eng = _long_engine(max_batch=4)
        built = set()

        def build(op, args):
            key = (op[0], len(args.get("slots", ())))
            if op[0] != "decode" or key in built:
                return 0.0
            built.add(key)
            return 16.0

        dev = _ScriptedDevice(eng, lambda kind, i: 0.133, build)
        _submit(eng, "a")
        while len(dev.chunks) < 12:
            eng._step()
        for rid in "bc":
            _submit(eng, rid)
        while len(dev.chunks) < 16:
            eng._step()
        assert built == {("decode", 2), ("decode", 4)}
        spans = dev.parents_arithmetic()
        count, total = _hist(eng.telemetry, "jetstream:decode_step_duration_seconds")
        # The four-row chunk's own first call is not timed; the two-row chunk
        # read behind its build is, at 16 s and more.
        # (With a slot still free and nobody waiting, the build began where
        # the held chunk went out: 20 ms before the end of the one in flight.)
        held = spans.index(max(spans))
        assert spans[held] == pytest.approx(16.0 + 0.133 - HOLD_MARGIN_S)
        assert count == len(spans) - 1
        assert total == pytest.approx(sum(spans) - spans[held + 1])
        assert _stall_seconds(eng) == {"device_wait": 0.0, "host": 0.0}
        assert not eng.stalls.ring

    run(body())


def test_one_heartbeat_class_fills_the_engines_and_the_gateways_histogram():
    """A handler that blocks the engine server's loop for 0.3 s shows in
    jetstream:event_loop_lag_seconds beyond 0.1 s and at or under 0.5 s,
    on either backend's server; the gateway's monitor is the same class and
    still fills router_loop_lag_seconds."""
    from aiohttp import web

    from llm_d_inference_scheduler_tpu.router.gateway import build_gateway
    from llm_d_inference_scheduler_tpu.router.metrics import REGISTRY
    from llm_d_inference_scheduler_tpu.router.schedpool import LoopLagMonitor

    port = PORT + 8

    async def block(request):
        time.sleep(0.3)
        return web.Response(text="done")

    def lag(registry, name, le):
        return _value(registry, name + "_bucket", le=le)

    async def body():
        server = EngineServer(_cfg("sim", port))
        server.app.router.add_get("/block", block)
        gw = build_gateway(f"pool:\n  endpoints:\n    - {{address: 127.0.0.1, "
                           f"port: {port}}}\n", port=port + 1, poll_interval=0.05)
        assert type(gw.loop_lag) is type(server.loop_lag) is LoopLagMonitor
        assert gw.loop_lag.histogram is not server.loop_lag.histogram
        await server.start()
        await gw.start()
        try:
            registry = server.engine.telemetry.registry
            names = ("jetstream:event_loop_lag_seconds", "router_loop_lag_seconds")
            was = [(lag(registry, names[0], "0.1"), lag(registry, names[0], "0.5")),
                   (lag(REGISTRY, names[1], "0.1"), lag(REGISTRY, names[1], "0.5"))]
            async with httpx.AsyncClient(timeout=30) as c:
                await asyncio.sleep(0.25)        # both heartbeats are asleep
                assert (await c.get(f"http://127.0.0.1:{port}/block")).text == "done"
                await asyncio.sleep(0.25)
                text = (await c.get(f"http://127.0.0.1:{port}/metrics")).text
            assert 'jetstream:event_loop_lag_seconds_bucket{le="0.5"}' in text
            # One loop carries both servers here, so both heartbeats felt it.
            for (low, high), (reg, name) in zip(was, zip((registry, REGISTRY), names)):
                assert lag(reg, name, "0.1") - low >= 2      # the quiet beats
                assert (lag(reg, name, "0.5") - high) \
                    - (lag(reg, name, "0.1") - low) == 1     # the blocked one
        finally:
            await gw.stop()
            await server.stop()

    run(body())


def test_a_pause_of_the_upstream_mid_stream_is_the_requests_longest_gap():
    """An upstream that pauses 0.4 s between two tokens of a stream: the
    gateway observes that stream's longest gap once, where it closes the
    request, in the bucket the pause belongs to."""
    from llm_d_inference_scheduler_tpu.router.gateway import build_gateway
    from llm_d_inference_scheduler_tpu.router.metrics import REGISTRY

    port = PORT + 10

    def gaps(le=None):
        name = "router_stream_gap_max_seconds"
        return (_value(REGISTRY, name + "_count") if le is None
                else _value(REGISTRY, name + "_bucket", le=le))

    async def body():
        server = EngineServer(_cfg("sim", port, sim_decode_ms_per_token=2.0))
        real_submit, relays = server.engine.submit, []

        def submit(req):
            src, dst = real_submit(req), asyncio.Queue()

            async def relay():
                for n in range(req.max_tokens + 1):
                    ev = await src.get()
                    if n == 5:
                        await asyncio.sleep(0.4)
                    dst.put_nowait(ev)

            relays.append(asyncio.get_running_loop().create_task(relay()))
            return dst

        server.engine.submit = submit
        gw = build_gateway(f"pool:\n  endpoints:\n    - {{address: 127.0.0.1, "
                           f"port: {port}}}\n", port=port + 1, poll_interval=0.05)
        await server.start()
        await gw.start()
        try:
            was = gaps(), gaps("0.3"), gaps("0.5")
            async with httpx.AsyncClient(timeout=30) as c:
                async with c.stream(
                        "POST", f"http://127.0.0.1:{port + 1}/v1/completions",
                        json={"model": "tiny", "prompt": "stream on",
                              "max_tokens": 12, "stream": True}) as r:
                    lines = [l async for l in r.aiter_lines()
                             if l.startswith("data: ")]
                assert r.status_code == 200 and lines[-1] == "data: [DONE]"
                unary = await c.post(
                    f"http://127.0.0.1:{port + 1}/v1/completions",
                    json={"model": "tiny", "prompt": "no stream", "max_tokens": 3})
                assert unary.status_code == 200
                text = (await c.get(f"http://127.0.0.1:{port + 1}/metrics")).text
            assert "router_stream_gap_max_seconds_count" in text
            assert gaps() == was[0] + 1           # once, the streamed one alone
            assert gaps("0.3") == was[1] and gaps("0.5") == was[2] + 1
        finally:
            for t in relays:
                t.cancel()
            await gw.stop()
            await server.stop()

    run(body())
