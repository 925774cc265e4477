"""Due-time timing and lateness against a fake server that is slow on
purpose: an open loop must charge a stalled request's wait to the requests
behind it, and say how late it ran."""

import asyncio
import json
import time

from aiohttp import web

import client
import stats
import traffic


async def _fake_completions(request: web.Request):
    """Streams max_tokens characters: first after 50 ms, the rest 10 ms
    apart in pieces of two."""
    body = await request.json()
    n = body["max_tokens"]
    resp = web.StreamResponse(headers={
        "Content-Type": "text/event-stream",
        client.SERVED_HEADER: "127.0.0.1:1"})
    await resp.prepare(request)
    await asyncio.sleep(0.05)
    sent = 0
    while sent < n:
        k = min(2, n - sent) if sent else 1
        chunk = {"choices": [{"text": "x" * k}]}
        await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
        sent += k
        await asyncio.sleep(0.01)
    usage = {"prompt_tokens": len(body["prompt"]) + 1, "completion_tokens": n,
             "prompt_tokens_details": {"cached_tokens": 16}}
    await resp.write(f"data: {json.dumps({'choices': [{'text': ''}], 'usage': usage})}\n\n".encode())
    await resp.write(b"data: [DONE]\n\n")
    return resp


async def _serve(handler):
    app = web.Application()
    app.router.add_post("/v1/completions", handler)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}"


def _req(i, tokens=5):
    return traffic.Req(f"r{i}", "p" * 9, 10, tokens)


def test_streamed_request_is_timed_from_due_and_counted_by_characters():
    async def go():
        runner, url = await _serve(_fake_completions)
        try:
            chains = [traffic.Chain(0.1 * i, iter([_req(i)])) for i in range(5)]
            return await asyncio.wait_for(client.run_window(
                url, "m", chains, 0.0, seconds=1.0, lead_s=0.2), timeout=20)
        finally:
            await runner.cleanup()

    records, _ = asyncio.run(go())
    assert len(records) == 5 and all(r.ok for r in records)
    for r in records:
        assert sum(n for _, n in r.pieces) == 5 and r.text == "xxxxx"
        assert r.cached_tokens == 16 and r.served_by == "127.0.0.1:1"
        assert 0.04 < r.ttft_s < 0.2
        assert 0.004 < r.tpot_s < 0.03      # 2 tokens / 10 ms, not 1 / 10 ms
    e2e = stats.end_to_end(records, 1.0, 1)
    assert e2e["out_tokens_per_s"] == 25.0


def test_a_late_generator_is_charged_to_the_request_and_reported():
    """A chain's second request is due when the first ends + think; block the
    loop for 150 ms right then: the send is late, TTFT counts from due."""
    async def go():
        runner, url = await _serve(_fake_completions)
        try:
            first = traffic.Req("a", "p" * 9, 10, 3, think_after_s=0.05)
            chain = traffic.Chain(0.0, iter([first, _req(1, 3)]))

            async def stall(t0):
                await asyncio.sleep(max(0, t0 + 0.12 - time.monotonic()))
                time.sleep(0.15)          # the generator's own fault

            return await asyncio.wait_for(client.run_window(
                url, "m", [chain], 0.0, seconds=5.0, lead_s=0.1,
                on_start=stall), timeout=20)
        finally:
            await runner.cleanup()

    records, _ = asyncio.run(go())
    second = [r for r in records if r.rid == "r1"][0]
    late = second.sent_s - second.due_s
    assert late > 0.05
    assert second.ttft_s > late + 0.04       # lateness + the server's 50 ms
    assert stats.generator_report(records, 5.0)["late_max_ms"] > 50


def test_nothing_is_sent_after_the_window_and_failures_are_records():
    async def refuse(request):
        return web.Response(status=503, text="full")

    async def go():
        runner, url = await _serve(refuse)
        try:
            chains = [traffic.Chain(0.0, iter([_req(0), _req(1)])),
                      traffic.Chain(2.0, iter([_req(2)]))]
            return await asyncio.wait_for(client.run_window(
                url, "m", chains, 0.0, seconds=1.0, lead_s=0.1), timeout=20)
        finally:
            await runner.cleanup()

    records, _ = asyncio.run(go())
    assert [r.rid for r in records] == ["r0", "r1"]     # r2 was due after 1.0
    assert all(r.status == 503 and not r.ok and "full" in r.error for r in records)
