"""The load: sends a plan's chains through the gateway, each request timed
from when it was DUE, and records what came back.

scripts/loadgen.py has the right idea (an open-loop schedule) and times each
request from the send; this is the corrected copy. One process, one event
loop, one HTTP client: a steady generator matters more than a fast one, and
how late it ran is reported beside the results.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time

import httpx

from traffic import Chain, Req

SERVED_HEADER = "x-gateway-destination-endpoint-served"


@dataclasses.dataclass
class Record:
    """One request as the client saw it. Times are seconds from the window's
    start on the client's monotonic clock."""
    rid: str
    session: int
    turn: int
    due_s: float
    sent_s: float
    prompt_tokens_meant: int
    max_tokens: int
    status: int = 0
    error: str = ""
    served_by: str = ""
    first_s: float | None = None      # first streamed text
    last_s: float | None = None       # last streamed text
    done_s: float | None = None       # stream closed
    # (arrival, characters) of each streamed piece: one character is one
    # token under the byte tokenizer (tokenizer.py decodes token by token).
    pieces: list = dataclasses.field(default_factory=list)
    text: str = ""
    prompt_tokens: int | None = None
    completion_tokens: int | None = None
    cached_tokens: int = 0

    @property
    def ok(self) -> bool:
        """200, every token asked for, and the prompt the generator meant."""
        return (self.status == 200 and not self.error
                and self.completion_tokens == self.max_tokens
                and self.prompt_tokens == self.prompt_tokens_meant
                and self.first_s is not None)

    @property
    def ttft_s(self) -> float | None:
        return None if self.first_s is None else self.first_s - self.due_s

    @property
    def tpot_s(self) -> float | None:
        """(last token - first token) / (tokens - 1): with decode_chunk 8,
        tokens arrive in bursts, so single gaps would measure the chunk."""
        if self.first_s is None or not self.completion_tokens \
                or self.completion_tokens < 2:
            return None
        return (self.last_s - self.first_s) / (self.completion_tokens - 1)


def body_of(model: str, req: Req, temperature: float, stream: bool = True) -> dict:
    return {"model": model, "prompt": req.prompt, "max_tokens": req.max_tokens,
            "temperature": temperature, "ignore_eos": True, "stream": stream,
            "request_id": req.rid}


async def send(client: httpx.AsyncClient, url: str, model: str, req: Req,
               temperature: float, t0: float, due_s: float,
               timeout_s: float = 120.0) -> Record:
    """One streamed completion; never raises: a failure is a failed record."""
    rec = Record(req.rid, req.session, req.turn, due_s,
                 time.monotonic() - t0, req.prompt_tokens, req.max_tokens)
    try:
        async with client.stream(
                "POST", url + "/v1/completions",
                json=body_of(model, req, temperature),
                timeout=timeout_s) as r:
            rec.status = r.status_code
            rec.served_by = r.headers.get(SERVED_HEADER, "")
            if r.status_code != 200:
                rec.error = (await r.aread())[:200].decode(errors="replace")
            async for line in (r.aiter_lines() if not rec.error else _none()):
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                now = time.monotonic() - t0
                chunk = json.loads(line[6:])
                piece = chunk["choices"][0].get("text", "")
                if piece:
                    if rec.first_s is None:
                        rec.first_s = now
                    rec.last_s = now
                    rec.pieces.append((now, len(piece)))
                    rec.text += piece
                usage = chunk.get("usage")
                if usage:
                    rec.prompt_tokens = usage.get("prompt_tokens")
                    rec.completion_tokens = usage.get("completion_tokens")
                    rec.cached_tokens = (usage.get("prompt_tokens_details")
                                         or {}).get("cached_tokens", 0)
    except (httpx.HTTPError, ValueError, KeyError, asyncio.TimeoutError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:200]
    rec.done_s = time.monotonic() - t0
    return rec


async def _none():
    return
    yield


async def run_chain(client, url: str, model: str, chain: Chain,
                    temperature: float, t0: float, seconds: float,
                    out: list[Record]) -> None:
    due = chain.start_s
    for req in chain.requests:
        if due >= seconds:       # nothing is due after the window's end
            return
        delay = t0 + due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = await send(client, url, model, req, temperature, t0, due)
        out.append(rec)
        due = rec.done_s + req.think_after_s


async def run_window(url: str, model: str, chains: list[Chain],
                     temperature: float, seconds: float, lead_s: float,
                     on_start=None, ramp_s: float = 0.0) -> tuple[list[Record], float]:
    """Runs every chain to its end; returns the records and the window's
    start on time.monotonic(). The window starts `lead_s` plus the ramp from
    now, so every task is asleep on its due time before the first is due."""
    # At least the earliest chain's lead, and the same for every seed where
    # the caller names the mix's ramp: set-up time should not vary with where
    # the last ramp arrival happened to fall.
    ramp = max(ramp_s, -min((c.start_s for c in chains), default=0.0))
    records: list[Record] = []
    limits = httpx.Limits(max_connections=None, max_keepalive_connections=64)
    async with httpx.AsyncClient(limits=limits) as client:
        t0 = time.monotonic() + lead_s + max(ramp, 0.0)
        tasks = [asyncio.create_task(run_chain(
            client, url, model, c, temperature, t0, seconds, records))
            for c in chains]
        side = (asyncio.create_task(on_start(t0))
                if on_start is not None else None)
        try:
            await asyncio.gather(*tasks)
            if side is not None:
                await side
        finally:
            for t in tasks + ([side] if side is not None else []):
                t.cancel()
    return records, t0


async def send_all(url: str, model: str, reqs: list[Req], temperature: float,
                   concurrency: int = 1) -> list[Record]:
    """Set-up traffic (warm-up groups, preload, probes): `concurrency` at a
    time, in order."""
    out: list[Record] = []
    sem = asyncio.Semaphore(concurrency)
    t0 = time.monotonic()

    async def one(client, req):
        async with sem:
            out.append(await send(client, url, model, req, temperature, t0,
                                  time.monotonic() - t0, timeout_s=900.0))

    async with httpx.AsyncClient() as client:
        # The semaphore hands out turns in order, so with concurrency 1 the
        # requests go one after another as listed.
        await asyncio.gather(*[one(client, r) for r in reqs])
    return out
