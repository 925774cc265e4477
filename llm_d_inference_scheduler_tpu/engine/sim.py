"""SimEngine: CPU-only engine simulator.

Plays the role llm-d-inference-sim plays in the reference's e2e suite
(/root/reference/config/manifests/vllm/sim-deployment.yaml, SURVEY §4): a pod
that looks exactly like a real engine to the router — same OpenAI surface,
same telemetry contract, same P/D handshake — with scripted latencies, so the
whole routing stack is testable without TPUs.
"""

from __future__ import annotations

import asyncio
import time
import uuid
from collections import OrderedDict
from typing import Any

from ..kvcache.pages import PageGeometry
from ..utils.hashing import chain_block_hashes
from .config import EngineConfig
from .request import EngineRequest, FinishReason, TokenEvent
from .telemetry import EngineTelemetry, PrefixHitLog
from .tokenizer import get_tokenizer

_LOREM = "lorem ipsum dolor sit amet "


class _HubOnlyKvEvents:
    """SSE-only kv-event publisher for the sim: same duck type as
    engine/kv_events.KvEventPublisher (the server attaches ``hub`` and the
    /kv_events route streams it) WITHOUT the ZMQ bind — a sim fleet in the
    test suite must not claim real TCP ports at serving-port+1000. This is
    what lets the router's precise-prefix KvBlockIndex (and the fleet's
    confirmed-index replication on top of it, router/fleet.py) run
    CPU-only against sims."""

    def __init__(self, engine_id: str):
        self.engine_id = engine_id
        self.hub = None  # attached by the engine server at start

    def publish(self, event: str, hashes: list[int]) -> None:
        if hashes and self.hub is not None:
            self.hub.push({"event": event, "engine_id": self.engine_id,
                           "hashes": hashes})

    def stored(self, hashes: list[int]) -> None:
        self.publish("stored", hashes)

    def removed(self, hashes: list[int]) -> None:
        self.publish("removed", hashes)

    def close(self) -> None:
        self.hub = None


class SimEngine:
    def __init__(self, cfg: EngineConfig):
        self.cfg = cfg
        self.mcfg = cfg.model_config
        self.engine_id = cfg.engine_id or f"sim-{uuid.uuid4().hex[:8]}"
        self.tokenizer = get_tokenizer(cfg.tokenizer, self.mcfg.vocab_size)
        self.model_name = cfg.model_name
        block = self.mcfg.kv_block_size
        self.n_blocks = PageGeometry.for_engine(
            self.mcfg, cfg.max_batch, cfg.max_model_len,
            cfg.hbm_kv_blocks).n_blocks
        self.telemetry = EngineTelemetry(block_size=block, num_blocks=self.n_blocks)
        self._sem = asyncio.Semaphore(cfg.max_batch)
        self._waiting = 0
        self._running = 0
        self._blocks_used = 0
        self.kv_exports: dict[str, dict[str, Any]] = {}
        self._tasks: dict[str, asyncio.Task] = {}
        self._gen_tokens = self.tokenizer.encode(_LOREM, add_bos=False)
        # Prefix-reuse accounting parity with the real engine: a
        # capacity-bounded LRU of served block hashes stands in for the
        # PrefixCachingAllocator, feeding the SAME PrefixHitLog surfaces
        # (x-kv-hit-* headers, the /debug/kv ring, the
        # jetstream:prefill_tokens / prefix_hit_tokens counter pair) so
        # warm repeat prompts confirm real hit depths CPU-only.
        self._prefix_lru: OrderedDict[int, None] = OrderedDict()
        self.kv_hits = PrefixHitLog(self.telemetry, block)
        # KV-event parity with the real engine (core.py): stored/removed
        # events for the served-block LRU plus the 1 s idempotent snapshot
        # re-publication that heals subscriber losses. SSE-hub-only (no
        # ZMQ bind); gated on the same resolved_kv_events_port knob.
        self.kv_events = (_HubOnlyKvEvents(self.engine_id)
                          if cfg.resolved_kv_events_port() else None)
        self._last_kv_snapshot = 0.0
        # Simulated KV-import measurements (the real engine's
        # kv_import_stats contract, engine/core.py): the server pops these
        # for the x-kv-pull-ms/-bytes response headers the sidecar relays
        # into the router's per-pair TransferTable. Bounded: entries are
        # popped at response time; streamed legs (whose headers leave
        # early) are swept by the cap.
        self.kv_import_stats: OrderedDict[str, dict[str, Any]] = OrderedDict()
        # Admission-wait parity (the real engine's queue_waits contract,
        # engine/core.py _record_queue_wait): measured around the
        # batch-slot semaphore, popped by the server for the
        # x-engine-queue-ms header. Same 512-entry sweep as above.
        self.queue_waits: OrderedDict[str, float] = OrderedDict()

    async def start(self):
        pass

    async def stop(self):
        pass

    def embed(self, ids: list[int]):
        """Deterministic unit vector from the token ids (llm-d-inference-sim
        analogue for /v1/embeddings e2e tests)."""
        import zlib

        import numpy as np

        seed = zlib.crc32(np.asarray(ids, np.int64).tobytes())
        rng = np.random.default_rng(seed)
        v = rng.normal(size=64).astype(np.float32)
        return v / max(float(np.linalg.norm(v)), 1e-6)

    def _update_gauges(self):
        self._sweep_exports()
        self._maybe_kv_snapshot()
        self.telemetry.waiting.set(self._waiting)
        self.telemetry.running.set(self._running)
        usable = max(self.n_blocks - 1, 1)
        self.telemetry.kv_usage.set(min(self._blocks_used / usable, 1.0))
        self.telemetry.free_blocks.set(max(usable - self._blocks_used, 0))
        self.telemetry.batch_fill.set(
            min(self._running / max(self.cfg.max_batch, 1), 1.0))

    def _sweep_exports(self):
        # Decoders can never pull real KV from a sim (kv_fetch is 501), so
        # unclaimed exports must expire or kv_usage ratchets to 1.0.
        from .core import KV_EXPORT_TTL_S
        now = time.monotonic()
        for rid in [r for r, rec in self.kv_exports.items()
                    if now - rec.get("created", now) > KV_EXPORT_TTL_S]:
            self.release_kv_export(rid)

    def submit(self, req: EngineRequest) -> asyncio.Queue:
        out: asyncio.Queue = asyncio.Queue()
        task = asyncio.get_running_loop().create_task(self._serve(req, out))
        self._tasks[req.request_id] = task
        task.add_done_callback(lambda _: self._tasks.pop(req.request_id, None))
        return out

    def idle(self) -> bool:
        """Drain gate: no live per-request task."""
        return not self._tasks

    def abort(self, request_id: str) -> None:
        task = self._tasks.get(request_id)
        if task is not None:
            task.cancel()

    def _commit_lru(self, hashes: list[int]) -> None:
        """Commit block hashes into the served-block LRU, publishing
        stored/removed kv events for the delta (the real allocator's
        publication points, core.py)."""
        stored = []
        for h in hashes:
            if h not in self._prefix_lru:
                stored.append(h)
            self._prefix_lru[h] = None
            self._prefix_lru.move_to_end(h)
        evicted = []
        while len(self._prefix_lru) > max(self.n_blocks, 1):
            evicted.append(self._prefix_lru.popitem(last=False)[0])
        if self.kv_events is not None:
            self.kv_events.stored(stored)
            self.kv_events.removed(evicted)

    def _maybe_kv_snapshot(self) -> None:
        """Idempotent 1 s re-publication of the whole served-block set
        (engine/core.py contract): SSE subscribers that dropped or missed
        `stored` events re-converge within one period."""
        if self.kv_events is None:
            return
        now = time.monotonic()
        if now - self._last_kv_snapshot < 1.0:
            return
        self._last_kv_snapshot = now
        self.kv_events.stored(list(self._prefix_lru))

    def _commit_prefix_blocks(self, req: EngineRequest) -> None:
        """Commit the prompt's block-hash chain into the served-block LRU
        without recording a hit — the P/D KV-import path: the decode pod
        really holds the blocks afterwards (a warm follow-up turn finds
        them), but an import is not a prefix-cache hit (engine/core.py
        contract — the import legs carry no x-kv-hit-* headers)."""
        block = self.mcfg.kv_block_size
        hashes = chain_block_hashes(self.model_name, req.prompt_token_ids,
                                    "", block)
        self._commit_lru(hashes)

    def _note_prefix_hit(self, req: EngineRequest) -> int:
        """Match the prompt's block-hash chain against the served-block LRU
        (consecutive from the start, >=1 suffix token kept — the same
        matchable-prefix rule as the real allocator), commit the prompt's
        complete blocks, and record the hit through the shared
        PrefixHitLog. Returns the hit depth in tokens."""
        block = self.mcfg.kv_block_size
        prompt = req.prompt_token_ids
        hashes = chain_block_hashes(self.model_name, prompt, "", block)
        max_match = (len(prompt) - 1) // block if prompt else 0
        match = 0
        for h in hashes[:max_match]:
            if h in self._prefix_lru:
                self._prefix_lru.move_to_end(h)
                match += 1
            else:
                break
        self._commit_lru(hashes)
        hit_tokens = match * block
        self.kv_hits.note(req.request_id, hit_tokens, len(prompt))
        return hit_tokens

    def release_kv_export(self, request_id: str) -> None:
        rec = self.kv_exports.pop(request_id, None)
        if rec:
            self._blocks_used -= rec["n_blocks"]
            self._update_gauges()

    async def _stream_prefill_export(self, req: EngineRequest, n_blocks: int,
                                     prompt_len: int, prefill_s: float,
                                     first: int) -> None:
        """Chunk-streamed remote-decode prefill: the export record is created
        UP FRONT (``chunks_staged=0``, ``complete=False``) and gains one chunk
        per simulated prefill window, so a decode peer long-polling the /kv
        chunk surface pulls chunk k while chunk k+1 "computes" — the same
        schedule the real engine's ``_maybe_stage_chunk`` runs, priced on CPU.
        The record owns the request's blocks from creation (the serve path
        zeroes its local count), so cancellation mid-stream releases exactly
        once — via ``release_kv_export`` here or the TTL sweep later."""
        block = self.mcfg.kv_block_size
        win = self.cfg.prefill_chunk
        win = max(block, (win + block - 1) // block * block) if win > 0 else 0
        rec: dict[str, Any] = {
            "n_blocks": n_blocks, "seq_len": prompt_len,
            "created": time.monotonic(), "first_token": first,
            "chunk_blocks": [], "chunks_staged": 0,
            "blocks_staged": 0, "complete": False}
        self.kv_exports[req.request_id] = rec
        try:
            rest = prompt_len
            while True:
                step = min(win, rest) if win else rest
                rest -= step
                await asyncio.sleep(prefill_s * step / max(prompt_len, 1))
                done = rest <= 0
                upto = (n_blocks if done
                        else min((prompt_len - rest) // block, n_blocks))
                cb = upto - rec["blocks_staged"]
                if cb > 0:
                    rec["chunk_blocks"].append(cb)
                    rec["blocks_staged"] = upto
                    rec["chunks_staged"] += 1
                if done:
                    rec["complete"] = True
                    return
        except asyncio.CancelledError:
            self.release_kv_export(req.request_id)
            raise

    def _pull_kv_chunks(self, ktp: dict[str, Any], rate: float,
                        block: int) -> dict[str, Any] | None:
        """Pipelined decode-side import (thread body): real HTTP long-polls
        against the prefill pod's /kv chunk surface, sleeping the per-block
        transfer cost per chunk — so the transfer genuinely overlaps the
        peer's remaining prefill in wall-clock, which is what the pd-pipeline
        bench measures. Returns kv_import_stats (with the non-overlapped
        ``exposed_ms``) or None on any failure (caller degrades to local
        prefill — zero client-visible errors)."""
        import httpx

        t0 = time.monotonic()
        url = (f"http://{ktp['remote_host']}:{ktp['remote_port']}"
               f"/kv/{ktp['remote_request_id']}")
        chunk = 0
        pulled = 0
        complete_at: float | None = None
        deadline = t0 + 60.0
        try:
            while True:
                if time.monotonic() > deadline:
                    return None
                r = httpx.get(url, params={"chunk": chunk, "wait_ms": 1000},
                              timeout=10.0)
                if r.status_code == 202:  # chunk not staged yet: re-poll
                    continue
                if r.status_code == 204:  # no further chunks
                    if complete_at is None:
                        complete_at = time.monotonic()
                    break
                r.raise_for_status()
                cb = int(r.headers.get("x-kv-chunk-blocks") or 0)
                done = r.headers.get("x-kv-complete") == "1"
                if done and complete_at is None:
                    complete_at = time.monotonic()
                time.sleep(rate * cb / 1000)
                pulled += cb
                chunk += 1
                if done and chunk >= int(
                        r.headers.get("x-kv-chunks-staged") or 0):
                    break
        except Exception:
            return None
        try:
            httpx.delete(url, timeout=5.0)
        except Exception:
            pass  # exporter TTL sweep reclaims
        t_end = time.monotonic()
        # Exposed = the tail of the pull that was NOT hidden behind the
        # peer's prefill: nothing before the first complete=1 observation
        # counts (the prefill engine was still computing anyway).
        exposed_s = t_end - max(complete_at if complete_at else t0, t0)
        return {"ms": (t_end - t0) * 1e3, "exposed_ms": exposed_s * 1e3,
                "bytes": pulled * block * 1024, "route": "sim-chunked"}

    async def _serve(self, req: EngineRequest, out: asyncio.Queue):
        self._waiting += 1
        self._update_gauges()
        t_queue = time.monotonic()
        try:
            await self._sem.acquire()
        except asyncio.CancelledError:  # aborted while queued
            self._waiting -= 1
            self._update_gauges()
            out.put_nowait(TokenEvent(
                request_id=req.request_id, token_id=None,
                finish_reason=FinishReason.ABORT,
                prompt_tokens=len(req.prompt_token_ids)))
            return
        # Admission wait = semaphore hold time (the sim's only queue).
        req.admit_time = time.monotonic()
        self.telemetry.queue_wait.observe(req.admit_time - t_queue)
        self.queue_waits[req.request_id] = (req.admit_time - t_queue) * 1e3
        while len(self.queue_waits) > 512:
            self.queue_waits.popitem(last=False)
        try:
            self._waiting -= 1
            self._running += 1
            prompt_len = len(req.prompt_token_ids)
            block = self.mcfg.kv_block_size
            n_blocks = -(-max(prompt_len + req.max_tokens, 1) // block)
            self._blocks_used += n_blocks
            self._update_gauges()
            ktp = req.kv_transfer_params or {}
            # P/D decode leg with a staged remote export: the KV arrives
            # over the (simulated) pull instead of being recomputed — sleep
            # the per-block transfer cost, commit the blocks (the pod
            # really holds them afterwards) and record no hit. Everything
            # else prefills locally, paying compute only for the tokens the
            # served-block LRU does NOT already hold — cache-hit prefills
            # are cheap, cold prefills expensive (the PPD premise the
            # multi-turn bench measures).
            imported = ((bool(ktp.get("remote_block_ids"))
                         or bool(ktp.get("stream_chunks")))
                        and not ktp.get("do_remote_decode"))
            chunked_pull = imported and bool(ktp.get("stream_chunks"))
            if imported:
                self._commit_prefix_blocks(req)
                # Per-peer transfer topology: the prefill peer that staged
                # the export (remote_host:remote_port) may carry its own
                # ms/block rate — skewed-pair benches price fast and slow
                # pairs differently; flat-scalar config is unchanged.
                rate = self.cfg.sim_kv_pull_ms_per_block
                peers = self.cfg.sim_kv_pull_ms_per_peer
                if peers:
                    rate = peers.get(
                        f"{ktp.get('remote_host')}:{ktp.get('remote_port')}",
                        rate)
                pull_s = 0.0
                if not chunked_pull:
                    n_pull = len(ktp["remote_block_ids"])
                    pull_s = rate * n_pull / 1000
                    self.kv_import_stats[req.request_id] = {
                        "ms": pull_s * 1e3,
                        "bytes": n_pull * block * 1024,  # nominal 1KiB/token
                        "route": "sim"}
                    while len(self.kv_import_stats) > 512:
                        self.kv_import_stats.popitem(last=False)
            else:
                hit_tokens = self._note_prefix_hit(req)
                pull_s = 0.0
            try:
                if chunked_pull:
                    stats = await asyncio.to_thread(
                        self._pull_kv_chunks, ktp, rate, block)
                    if stats is not None:
                        self.kv_import_stats[req.request_id] = stats
                        while len(self.kv_import_stats) > 512:
                            self.kv_import_stats.popitem(last=False)
                    else:
                        # Prefill peer died mid-stream: recompute the
                        # prefill locally (reference fallback semantics) —
                        # the client still gets its tokens.
                        await asyncio.sleep(self.cfg.sim_prefill_ms_per_token
                                            * prompt_len / 1000)
                elif imported:
                    await asyncio.sleep(pull_s)
                else:
                    cold_tokens = max(prompt_len - hit_tokens, 0)
                    prefill_s = (self.cfg.sim_prefill_ms_per_token
                                 * cold_tokens / 1000)
                    if (ktp.get("do_remote_decode")
                            and ktp.get("stream_chunks")):
                        n_export = n_blocks
                        n_blocks = 0  # owned by the export from creation
                        await self._stream_prefill_export(
                            req, n_export, prompt_len, prefill_s,
                            self._gen_tokens[0])
                    else:
                        await asyncio.sleep(prefill_s)
                    # Import legs record no prefill-step sample (the real
                    # engine observes only actual prefill dispatches — a
                    # zero-valued sample would drag the histogram's
                    # quantiles to ~0 on P/D decode pods).
                    self.telemetry.prefill_step.observe(prefill_s)
                self.telemetry.prompt_tokens.inc(prompt_len)
                now = time.monotonic()
                self.telemetry.ttft.observe(now - req.arrival_time)
                self.telemetry.admit_to_first_token.observe(now - req.admit_time)
                first = self._gen_tokens[0]
                if ktp.get("do_remote_decode"):
                    rec = self.kv_exports.get(req.request_id)
                    if rec is None:  # serial 2-phase: stage at completion
                        rec = {"n_blocks": n_blocks, "seq_len": prompt_len,
                               "created": time.monotonic()}
                        self.kv_exports[req.request_id] = rec
                        n_blocks = 0  # retained by the export, not released below
                    block_ids = list(range(rec["n_blocks"]))
                    out.put_nowait(TokenEvent(
                        request_id=req.request_id, token_id=first,
                        text=self.tokenizer.decode([first]),
                        finish_reason=FinishReason.LENGTH, is_first=True,
                        kv_transfer_params={
                            "remote_engine_id": self.engine_id,
                            "remote_request_id": req.request_id,
                            "remote_block_ids": block_ids,
                            "remote_seq_len": prompt_len,
                            "remote_first_token": first,
                            "remote_host": self.cfg.host,
                            "remote_port": self.cfg.port,
                        },
                        prompt_tokens=prompt_len, completion_tokens=1))
                    self.telemetry.request_success.labels(
                        finished_reason=FinishReason.LENGTH.value).inc()
                    return

                n = max(req.max_tokens, 1)
                for i in range(n):
                    await asyncio.sleep(self.cfg.sim_decode_ms_per_token / 1000)
                    tok = self._gen_tokens[i % len(self._gen_tokens)]
                    self.telemetry.decode_step.observe(
                        self.cfg.sim_decode_ms_per_token / 1000)
                    self.telemetry.generation_tokens.inc()
                    out.put_nowait(TokenEvent(
                        request_id=req.request_id, token_id=tok,
                        text=self.tokenizer.decode([tok]), is_first=(i == 0),
                        prompt_tokens=prompt_len, completion_tokens=i + 1))
                out.put_nowait(TokenEvent(
                    request_id=req.request_id, token_id=None,
                    finish_reason=FinishReason.LENGTH,
                    prompt_tokens=prompt_len, completion_tokens=n))
                self.telemetry.request_success.labels(
                    finished_reason=FinishReason.LENGTH.value).inc()
            except asyncio.CancelledError:
                out.put_nowait(TokenEvent(
                    request_id=req.request_id, token_id=None,
                    finish_reason=FinishReason.ABORT,
                    prompt_tokens=prompt_len))
                self.telemetry.request_success.labels(
                    finished_reason=FinishReason.ABORT.value).inc()
            finally:
                self._running -= 1
                self._blocks_used -= n_blocks
                self._update_gauges()
        finally:
            self._sem.release()
