"""TpuEngine: continuous-batching JAX engine (the model-server half).

Architecture (TPU-first, JetStream-style):
- One engine thread owns the device: it alternates admission/prefill with
  batched decode steps. aiohttp handlers talk to it through thread-safe
  submission + per-request asyncio queues (events hop back to the event loop
  via call_soon_threadsafe).
- Decode runs one jit-compiled step over a FIXED batch of slots (static
  shapes). Inactive slots point their block tables at the trash block 0, so
  no masking branches exist on the hot path; their lanes are dead compute.
- Prefill pads prompts to power-of-two buckets (bounded compile cache) and
  scatters KV into the slot's pages inside the same jit (donated buffers →
  in-place HBM updates).
- P/D disaggregation (reference behavior:
  /root/reference/pkg/sidecar/proxy/connector_nixlv2.go:109-253):
  prefills tagged do_remote_decode host-stage their KV for pickup (exports
  swept by TTL); decode-side imports fetch KV on a separate thread so the
  engine thread never blocks on the network, then scatter on-device.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import functools
import json
import logging
import os
import threading
import time
import uuid
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kvcache import pages, state as state_pool, wire
from ..models import bind, scopes
from ..utils.hashing import chain_block_hashes
from .blocks import (PrefixCachingAllocator, allocator_for, table_groups,
                     window_table_groups)
from .config import EngineConfig
from .multihost import ChannelBroken
from .request import EngineRequest, FinishReason, TokenEvent
from .sampling import sample_tokens
from .telemetry import LOOP_PHASES, EngineTelemetry, LoopStalls, PrefixHitLog
from .tokenizer import get_tokenizer

log = logging.getLogger("engine.core")

KV_EXPORT_TTL_S = 60.0
# The most prompt tokens _advance_prefills writes ahead of one decode chunk:
# the stated stretch of the decode cadence under prefill_chunk (a prompt of
# this length prefilled whole makes the lanes wait as long).
PREFILL_STEP_TOKENS = 4096
# How long before the chunk in flight is reckoned to end a held chunk goes out
# (_hold_for_arrival). Sized on the chip (qwen3-4b, 16 lanes x 2,048, chunks
# of 8 steps, 111 ms; PERF.md section 6, PR 40): from the deadline to "chunk
# enqueued" the loop took 4.1-4.7 ms at the median (waking up, 0.1 ms of
# decode_prepare, 3.3-3.8 ms of decode_dispatch), 6.6-9.5 ms at the fifth
# largest of a hundred and 9.5 ms at most inside a measured window, a 5 ms
# turn of the GIL among them; with 20 ms the read of the chunk ahead then
# still blocked for 11.8 ms at least. Err early: a chunk that goes out late
# idles the device and lengthens every running stream, one that goes out
# early only costs an arrival its place.
HOLD_MARGIN_S = 0.020
# A shape's expected device time is the least of its last clean periods: a
# period can only read longer than the chunk took (a late read, a late chunk).
HOLD_PERIODS = 8
# A short chunk is decode_chunk over one of these, the shortest the host's work
# fits in (_chunk_steps: a slot open, nobody waiting). Sized on the chip
# (qwen3-4b, 16 lanes x 2,048, --decode-chunk 8; PERF.md section 6, PR 57).
# The ONE decode program of the 8-lane bucket, five lanes live, at 2 / 4 / 8
# steps: 27.3 / 51.7 / 100.4 ms, 12.18 ms a step and 2.96 ms a chunk whatever
# its length, which was the stacked wq and wk copied whole into the order of
# axes the layer loop reads; held in that order (_laid_out) 24.6 / 49.0 /
# 97.8 ms, 12.21 a step and 0.14 a chunk. So a quarter costs a running
# stream nothing any more: in qwen3-4b.chat-steady (3.6 requests/s, four
# seeds a tree) chunks of 4 steps on the tree before read ttft_p50_ms
# 83.7-85.6 and tpot_p95_ms 18.0-18.7, chunks of 2 steps here 62.2-68.6 and
# 16.9-17.7, 99.9-100% of the chunks quarters. (PR 43 had read a quarter at
# 63.7-66.3 and +4.1 to +5.8% of tpot_p95_ms, the copies four times a
# period, and kept to a half.)
SHORT_CHUNK_DIVS = {"quarter": 4, "half": 2}
# What a short chunk's reckoned time must leave the loop beyond its own
# measured work a period (_chunk_steps): the most the loop was seen late by
# (above: 9.5 ms from a deadline to "chunk enqueued", a turn of the GIL in
# it, where the median, which the measured work holds, is 4.1-4.7). This
# keeps the DEVICE fed: a chunk goes out while the one before it runs, so a
# loop that needs its work and this much inside a chunk never leaves the
# queue empty. It is not HOLD_MARGIN_S, which says how early a HELD chunk
# goes out and holds the dispatch itself: asked of a quarter's 24.6 ms with
# the host's 7 (dispatch in both) it left nothing, and no quarter was ever
# dispatched (PERF.md section 6, PR 57). Where the work fits and the hold's
# margin does not, the chunk is short and simply not held.
KEEP_UP_S = 0.010


def _tcp_preflight(address: str, timeout: float = 2.0) -> None:
    """The transfer layer blocks indefinitely on an unreachable peer; fail
    fast so fallbacks engage (and, for coordinated multi-host pulls, so the
    leader never broadcasts a pull op that would wedge the followers)."""
    import socket

    host, _, port = address.rpartition(":")
    with socket.create_connection((host, int(port)), timeout=timeout):
        pass

# One transfer server per process (shared by colocated engines): multiple
# servers on one PJRT client abort in the aux socket layer, and production
# runs one engine per chip/process anyway.
_TRANSFER_SERVER = None
_TRANSFER_SERVER_LOCK = threading.Lock()


def _get_transfer_server(host: str):
    global _TRANSFER_SERVER
    with _TRANSFER_SERVER_LOCK:
        if _TRANSFER_SERVER is None:
            from jax.experimental import transfer as jax_transfer

            # The client is the process's, not one device's: engines on
            # different local devices share it. The bulk data rides a
            # socket bound on the engine's host: with no transport address
            # the server falls back to an in-process transport, and a pull
            # from another process then aborts the EXPORTER
            # (streaming.cc "local_bulk_transports_" check) — seen on the
            # CPU backend and between two chips of one v5e host.
            _TRANSFER_SERVER = jax_transfer.start_transfer_server(
                jax.local_devices()[0].client, "[::]:0", [f"{host}:0"])
        return _TRANSFER_SERVER


def _named(fn, name: str):
    """Give a step function the name its program carries in a profiler trace
    (`jit_<name>`), so a reduction finds a prefill after any refactor."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@dataclasses.dataclass
class _Slot:
    req: EngineRequest
    out: asyncio.Queue
    loop: asyncio.AbstractEventLoop
    blocks: list[int]
    position: int              # next token position to be written (booked)
    generated: list[int]
    # Decode steps dispatched for this slot and not yet booked: the chunk in
    # flight, and while the one before it is being read, that one too.
    ahead: int = 0
    first_emitted: bool = False
    cached_tokens: int = 0
    block_hashes: list[int] = dataclasses.field(default_factory=list)
    # Pipelined prefill: the fused prefill jit's sampled first token, still on
    # device (host transfer in flight). The slot joins the chunk dispatched
    # right behind its prefill (that chunk takes the token from the device,
    # TpuEngine._slot_tokens); _finalize_prefills() lands it on the host
    # afterwards, off the dispatch critical path.
    pending_tok: Any = None
    prompt_len: int = 0
    # The prompt is written in windows (_write_prefill_window): while True
    # the slot is excluded from decode batches. A prompt of one window is
    # written as it is admitted; a longer one a window at a time between
    # decode chunks (_advance_prefills), so long prompts never stall the
    # decode lanes for their full length.
    prefilling: bool = False
    prefill_rest: list[int] = dataclasses.field(default_factory=list)
    prefill_written: int = 0
    # (hashes, caching) — prefix-cache commit + KV-event publication wait
    # for the last window.
    chunk_meta: Any = None


@dataclasses.dataclass
class _Chunk:
    """A decode chunk dispatched and not yet read."""
    toks: Any                        # [K, B] sampled tokens, on the device
    steps: int                       # the rows of toks that are its own
    lanes: list[tuple[int, _Slot]]   # lane -> (slot index, the slot it held)
    t0: float                        # the loop's clock at dispatch
    timed: bool                      # its shape was built before: observe it
    shape: str                       # "lanes x table width", its program's
    behind: int                      # device calls queued since the chunk before


@dataclasses.dataclass
class _PendingImport:
    req: EngineRequest
    out: asyncio.Queue
    loop: asyncio.AbstractEventLoop
    payload: bytes | None = None
    headers: dict[str, str] | None = None
    # Device-to-device path: KV arrives as on-device arrays, no payload.
    k_dev: Any = None
    v_dev: Any = None
    # Multi-host path: the pull is a coordinated op executed on the engine
    # thread (every process participates); the fetch thread only preflights.
    dist_pull: bool = False
    error: str | None = None


class TpuEngine:
    """Continuous-batching engine over a models/ block (models.family) with
    a paged KV or latent cache on HBM."""

    def __init__(self, cfg: EngineConfig, params=None):
        self.cfg = cfg
        self.mcfg = cfg.model_config
        if (not cfg.checkpoint_path and params is None
                and os.path.isfile(os.path.join(cfg.model, "model_config.json"))):
            # model names a converted-checkpoint dir (convert_hf.py output):
            # its weights ARE the checkpoint.
            cfg.checkpoint_path = cfg.model
        self.engine_id = cfg.engine_id or f"tpu-{uuid.uuid4().hex[:8]}"
        # The device this engine is bound to: a single-device engine keeps
        # its weights, pages and step inputs there; a mesh engine's slice
        # starts there. Several engines in one process (one per local
        # device) differ only in device_index.
        if not 0 <= cfg.device_index < len(jax.local_devices()):
            raise ValueError(
                f"device_index {cfg.device_index}: this process sees "
                f"{len(jax.local_devices())} {jax.default_backend()} "
                "device(s)")
        self.device = jax.local_devices()[cfg.device_index]
        # The model's cache (kvcache/: its pages, and what it keeps a slot
        # beside them) and how a decode step attends over it.
        self.geom = pages.PageGeometry.for_engine(
            self.mcfg, cfg.max_batch, cfg.max_model_len, cfg.hbm_kv_blocks)
        if self.geom.one_chip_only:
            self._refuse_beyond_one_chip()
        cfg.pallas_attention = pages.use_kernel(
            self.geom.shape[-1], asked=cfg.pallas_attention,
            interpret=cfg.pallas_interpret, platform=self.device.platform,
            sharded=cfg.tp_size > 1 or cfg.ep_size > 1)
        self._decode_attention = pages.attention_for(
            self.geom, kernel=cfg.pallas_attention,
            interpret=cfg.pallas_interpret)
        # The model's family as this engine serves it (models/binding.py):
        # the block's module, the configuration its programs trace with (each
        # kernel in the form this device calls for), what a program counts as.
        self.bound = bind(
            self.mcfg, platform=self.device.platform,
            interpret=cfg.pallas_interpret,
            sharded=(cfg.tp_size > 1 or cfg.ep_size > 1 or cfg.pp_size > 1
                     or cfg.dist_num_processes > 1))
        self.model, self.mcfg = self.bound.module, self.bound.mcfg
        self.tokenizer = get_tokenizer(cfg.tokenizer, self.mcfg.vocab_size)
        self.model_name = cfg.model_name

        block = self.geom.block
        self.n_blocks = self.geom.n_blocks
        self.max_blocks_per_seq = self.geom.max_blocks_per_seq
        # (Which allocator is the cache's to say: engine/blocks.py.)
        self.allocator = allocator_for(self.geom, cfg.enable_prefix_caching)
        self.telemetry = EngineTelemetry(block_size=block, num_blocks=self.n_blocks)
        self.telemetry.watch_xla_builds()

        # Optional TP-sharded serving: params follow Megatron TP pspecs, KV
        # pages shard the kv-head axis (parallel/serve.py). tp_size=1 keeps
        # the plain single-device layout. Single-process meshes span exactly
        # tp*ep devices (dp=1); multi-host (dist_*) meshes span ALL global
        # devices — the dp axis holds the remainder as replicas (host inputs
        # are fed fully-replicated, see _put).
        self._dist = bool(cfg.dist_coordinator) and cfg.dist_num_processes > 1
        # jax.experimental.transfer server: stages prefilled KV on-device for
        # direct device-to-device pulls (ICI/DCN). Created BEFORE the
        # instruction channel so a follower's one-time hello can announce its
        # transfer address (sharded exports address every process's server).
        self.kv_transfer_server = None
        self._transfer_conns: dict[str, Any] = {}
        self._transfer_lock = threading.Lock()
        self.kv_import_device_count = 0  # diagnostics: pulls over ICI/DCN
        self.kv_import_host_count = 0    # diagnostics: host-staged HTTP fetches
        # The last device pull that failed over to the host path, in the
        # exception's own words (describe() carries it to /health).
        self.kv_import_device_error: str | None = None
        # Per-request KV pull stats (request_id -> {ms, bytes, route}):
        # written by the fetch thread, read (popped) by the server when it
        # stamps x-kv-pull-ms/-bytes on the decode response — the measured
        # per-pair transfer cost the router's /debug/transfers table
        # aggregates. Bounded ring; individually GIL-atomic dict/deque ops.
        self.kv_import_stats: dict[str, dict[str, Any]] = {}
        self._kv_import_order: collections.deque[str] = collections.deque()
        # Per-request admission wait (request_id -> ms): submit() stamps
        # the enqueue instant, the FIRST _admit pop measures the wait —
        # first-pop-wins, so a KV-fetch re-insert (same admission resumed,
        # not a new one) never re-measures — and the server pops the value
        # for the x-engine-queue-ms response header. Bounded rings;
        # individually GIL-atomic dict/deque ops.
        self._queue_submit: dict[str, float] = {}
        self.queue_waits: dict[str, float] = {}
        self._queue_wait_order: collections.deque[str] = collections.deque()
        # Per-request ACTUAL prefix-hit accounting (telemetry.PrefixHitLog,
        # shared with the sim), recorded once at prefill admission — the
        # engine-confirmed number the router's prefix scorers only PREDICT.
        # The server pops entries for the x-kv-hit-blocks/-tokens response
        # headers, reads them for usage.prompt_tokens_details, and serves
        # the bounded ring at GET /debug/kv.
        self.kv_hits = PrefixHitLog(self.telemetry, self.mcfg.kv_block_size)
        # Why "auto" ended on the host path, in the exception's own words
        # (None while the device wire is up, or was never asked for).
        self.kv_transfer_error: str | None = None
        if cfg.kv_transfer in ("auto", "device"):
            try:
                self.kv_transfer_server = _get_transfer_server(cfg.host)
            except Exception as e:
                if cfg.kv_transfer == "device":
                    raise
                self.kv_transfer_error = f"{type(e).__name__}: {e}"
        if self.kv_transfer_server is not None:
            log.info("kv handoff wire: device pull, host-staged HTTP as "
                     "fallback")
        else:
            log.info("kv handoff wire: host-staged HTTP only (kv_transfer="
                     "%s; transfer server: %s)", cfg.kv_transfer,
                     self.kv_transfer_error or "not asked for")
        # Host-staged shard wire (engine/shard_wire.py): the cross-process
        # transport for sharded exports when the jax transfer backend can't
        # carry them. kv_wire "auto" resolves to "host" on the cpu backend —
        # jax.experimental.transfer's cpu backend fatally crashes (local bulk
        # transport) or hangs (socket transport) on same-host cross-process
        # pulls — and to "device" on real TPU meshes.
        self.kv_shard_wire = None
        self._kv_wire = cfg.kv_wire
        if self._kv_wire == "auto":
            self._kv_wire = ("host" if jax.default_backend() == "cpu"
                             else "device")
        if self._dist and self._kv_wire == "host":
            # Only the active wire runs a server — on device-wire TPU meshes
            # nothing would ever pull from (or register on) the host wire.
            from .shard_wire import ShardWireServer

            self.kv_shard_wire = ShardWireServer(cfg.host)
        self._instr_channel = None
        if self._dist:
            # jax.distributed.initialize must already have run (server main /
            # multihost.maybe_init_distributed) — jax.devices() is global here.
            from .multihost import InstructionChannel

            self._instr_channel = InstructionChannel(
                leader=cfg.dist_process_id == 0,
                host=cfg.dist_instr_host or cfg.host,
                port=cfg.dist_instr_port,
                n_followers=cfg.dist_num_processes - 1,
                recv_timeout=cfg.dist_recv_timeout_s,
                hello={"process_id": cfg.dist_process_id,
                       "shard_wire_address":
                           (self.kv_shard_wire.address()
                            if self.kv_shard_wire is not None else None),
                       "transfer_address":
                           (self._transfer_address()
                            if self.kv_transfer_server is not None else None)})
            if self._instr_channel.leader:
                self._instr_channel.on_peer_lost = self._on_follower_lost
        self.mesh = None
        self.pp_mesh = None
        if cfg.pp_size > 1:
            from ..parallel.pp_serve import make_pp_mesh, validate_pp

            validate_pp(self.mcfg, cfg.pp_size, cfg.tp_size, cfg.ep_size)
            n_model = cfg.pp_size * cfg.tp_size * cfg.ep_size
            if self._dist:
                # Stage ring spanning hosts (the deployment this is for: a 70B
                # pipeline across a multi-host slice). The global device
                # list orders process-major, so the (pp, tp) reshape puts
                # consecutive stages on consecutive hosts: tp collectives
                # ride intra-host ICI, the ppermute stage hop crosses hosts
                # once per turn. Every process's devices must be in the
                # mesh — an SPMD process with no addressable device in the
                # computation cannot participate.
                if n_model != len(jax.devices()):
                    raise ValueError(
                        f"multi-host pp needs pp*tp*ep == global devices "
                        f"({n_model} != {len(jax.devices())})")
                self.pp_mesh = make_pp_mesh(jax.devices(), cfg.pp_size,
                                            tp=cfg.tp_size, ep=cfg.ep_size)
            else:
                self.pp_mesh = make_pp_mesh(
                    jax.local_devices()[cfg.device_index:][:n_model],
                    cfg.pp_size, tp=cfg.tp_size, ep=cfg.ep_size)
        elif cfg.tp_size > 1 or cfg.ep_size > 1 or self._dist:
            from ..parallel.serve import make_serve_mesh, validate_tp

            validate_tp(self.mcfg, cfg.tp_size, cfg.ep_size)
            n_model = cfg.tp_size * cfg.ep_size
            devices = jax.devices() if self._dist \
                else jax.local_devices()[cfg.device_index:][:n_model]
            self.mesh = make_serve_mesh(devices, tp=cfg.tp_size,
                                        ep=cfg.ep_size)

        # The stacked weights a one-chip TPU engine holds in the layout its
        # decode program chose, by name: their axes from major to minor as
        # the arrays lie (_laid_out); nothing on any other engine.
        self.weight_layouts: dict[str, list[int]] = {}
        if params is not None or cfg.checkpoint_path:
            if params is None:
                from .checkpoint import load_params

                params = load_params(cfg.checkpoint_path, self.mcfg)
            if self.mesh is not None:
                # Checkpoint-loaded / caller-passed params land unsharded.
                from ..parallel.serve import serve_shardings

                shardings, _ = serve_shardings(self.mcfg, self.mesh)
                params = jax.device_put(params, shardings)
            elif self.pp_mesh is not None:
                from ..parallel.pp_serve import shard_params_pp

                params = shard_params_pp(params, self.mcfg, self.pp_mesh)
            else:
                params = self._laid_out(jax.device_put(params, self.device))
            self.params = params
        elif self.mesh is not None:
            from ..parallel.serve import init_sharded_params

            self.params = init_sharded_params(self.mcfg, self.mesh,
                                              jax.random.key(cfg.seed))
        elif self.pp_mesh is not None:
            from ..parallel.pp_serve import init_pp_params

            self.params = init_pp_params(self.mcfg, self.pp_mesh,
                                         jax.random.key(cfg.seed))
        else:
            # One jitted program straight into the bound device: run eagerly,
            # each stacked tensor is an f32 normal, a scaled copy and a cast
            # — for Qwen3-4B's w3 about 7 GB of transients beside 6 GB
            # already held, which a 16 GB chip does not have.
            from jax.sharding import SingleDeviceSharding

            self.params = self._laid_out(jax.jit(
                lambda k: self.model.init_params(self.mcfg, k),
                out_shardings=SingleDeviceSharding(self.device))(
                    jax.random.key(cfg.seed)))
        mesh = self._page_mesh()
        self.k_pages, self.v_pages = (
            pages.alloc(self.geom, sharding=pages.page_sharding(mesh))
            if mesh is not None
            else pages.alloc(self.geom, device=self.device))

        self.warming = cfg.warmup  # cleared by the engine thread post-compile
        # Set by the engine thread when it cannot go on (a warm-up that
        # raised): the thread has stopped and the process should too.
        self.fatal: BaseException | None = None
        # Multi-host degrade latch: set when a follower dies (peer monitor)
        # or the instruction channel breaks mid-broadcast. Issuing further
        # collectives would deadlock, so the engine aborts everything and
        # refuses work; /health reports 503 for the restart controller.
        self.dist_degraded = False
        self.slots: list[_Slot | None] = [None] * cfg.max_batch
        self._waiting: list[tuple[EngineRequest, asyncio.Queue, asyncio.AbstractEventLoop]] = []
        self._import_ready: list[_PendingImport] = []
        self._abort_ids: set[str] = set()
        self._cond = threading.Condition()
        self._stop = False
        self._thread: threading.Thread | None = None
        self._sample_key = self._on_device(jax.random.key(cfg.seed + 1))
        # Host-staged KV exports for P/D handoff: request_id -> record.
        # Guarded by _exports_lock: written by the engine thread, read/popped
        # by the aiohttp event-loop thread (kv_fetch / kv_release).
        self.kv_exports: dict[str, dict[str, Any]] = {}
        self._exports_lock = threading.Lock()
        self.kv_events = None
        self._last_kv_snapshot = 0.0
        ev_port = cfg.resolved_kv_events_port()
        if ev_port:
            from .kv_events import KvEventPublisher

            try:
                self.kv_events = KvEventPublisher(ev_port, self.engine_id,
                                                  host=cfg.host)
            except Exception:
                log.exception("kv-event publisher disabled (bind failed)")
        # Device-to-device KV handoff (the NIXL-v2 analogue for TPU): a
        # Sharded engines stage/pull KV per unique page shard (kv_shards.py,
        # the NIXL multi-rank-descriptor analogue); the host-staged HTTP path
        # stays as fallback for single-process engines (reference
        # connector_nixlv2.go:109-253 control shape preserved).
        self._jit_stage = None
        # (op, shape-bucket) keys already dispatched once: the first call of
        # a novel key is a jit trace+compile — counted as a compile event;
        # later calls feed the step-duration histograms.
        self._seen_op_shapes: set[tuple[str, str]] = set()
        self._embed_fns: dict[int, Any] = {}
        self._embed_fns_lock = threading.Lock()
        # Multi-host embeddings: queued by embed() (HTTP executor thread),
        # drained by the engine thread so the op broadcast stays in order.
        self._embed_reqs: list[tuple] = []
        # P/D imports currently in their off-thread fetch window (popped
        # from _waiting, not yet on _import_ready) — counted so idle()
        # never declares the engine drained mid-transfer.
        self._kv_fetching = 0
        self._release_reqs: list[tuple[str, str]] = []
        self._prefill_fns: dict[int, Any] = {}
        # Decode shape ("lanes x table width") -> whether the program built
        # for it holds the Pallas call; filled at a shape's first dispatch
        # on engines that resolved pallas_attention on (see _op_decode).
        self.decode_kernel_in_program: dict[str, bool] = {}
        if self.pp_mesh is not None:
            from ..parallel.pp_serve import make_pp_decode_chunk

            # Dispatches per traced batch bucket: lane-group interleave
            # (no (P-1)/P wasted slab work / KV reads) whenever the bucket
            # splits evenly into stage groups, broadcast ring otherwise
            # (e.g. the B=1 single-stream bucket).
            self._jit_decode_chunk = make_pp_decode_chunk(
                self.mcfg, self.pp_mesh, cfg.decode_chunk)
        else:
            self._jit_decode_chunk = jax.jit(self._decode_chunk_impl,
                                             donate_argnums=(3, 4))
        def kv_import(kp, vp, blocks, k_new, v_new):  # `jit_kv_import`
            return pages.scatter_blocks(kp, vp, blocks, k_new, v_new)

        self._jit_import = jax.jit(kv_import, donate_argnums=(0, 1))
        # Every slot's newest sampled token, on the device: a prefill leaves
        # its first token here and a chunk its last step's, so the next chunk is
        # dispatched before either has reached the host. Index max_batch is
        # nobody's (padding lanes, samples nobody decodes from): out of
        # range, so it reads 0 and a write to it is dropped.
        self._slot_tokens = self._put(np.zeros((cfg.max_batch,), np.int32))

        def keep_tokens(table, slots, toks, row):   # toks [N], or [K, N]
            return table.at[slots].set(toks.reshape(-1, slots.size)[row],
                                       mode="drop")

        def slot_tokens(table, slots):
            return table.at[slots].get(mode="fill", fill_value=0)

        self._jit_keep_tokens = jax.jit(keep_tokens)
        self._jit_slot_tokens = jax.jit(slot_tokens)
        # The chunk dispatched and not yet read (_step keeps one in flight
        # while it reads and books the one before), and when the last one
        # was read, on the loop's clock.
        self._inflight: _Chunk | None = None
        # (slot index, request) of every request whose slot a successor took
        # while its last chunk was unread (_admit, _place): served through
        # that chunk's lanes, and gone from here once it is booked, later in
        # the same step. Here for a step that fails in between (_abort_all).
        self._retired: list[tuple[int, _Slot]] = []
        self._last_readback = 0.0
        self._clock = time.monotonic
        # What _hold_until reckons the end of the chunk in flight from: when
        # the first tokens of the prefills queued ahead of it were read (it
        # started no sooner), the device calls made since the last chunk went
        # out (they sit ahead of the next), and for every decode shape and
        # length in steps the periods of its last chunks that had no such
        # call ahead of them: their device time, as _land_chunk measured it.
        # Beside them what the loop itself did in each of the last periods
        # (every phase but the waits), which _chunk_steps reckons with.
        self._first_tokens_read = 0.0
        self._calls_since_chunk = 0
        self._chunk_times: dict[tuple[str, int], collections.deque] = {}
        self._host_work: collections.deque = collections.deque(
            maxlen=HOLD_PERIODS)
        # The period now running (from the last readback, or from the
        # dispatch of a chunk that went out alone): seconds by phase, prefills
        # finalized, whether a shape ran for the first time in it; judged at
        # its readback (_land_chunk).
        self.stalls = LoopStalls(self.telemetry)
        self._begin_period()
        log.info("engine %s up: %s", self.engine_id,
                 json.dumps(self.describe()))

    def _refuse_beyond_one_chip(self) -> None:
        """A cache that lives on the unsharded one-chip engine alone
        (kvcache/pages.py PageGeometry.one_chip_only says which, and why):
        asked for sharding, stages, processes or a role, say so now, by
        name, rather than serve something else."""
        cfg = self.cfg
        asked = [f"{name}={value}" for name, value, plain in (
            ("tp_size", cfg.tp_size, 1), ("ep_size", cfg.ep_size, 1),
            ("pp_size", cfg.pp_size, 1),
            ("dist_num_processes", cfg.dist_num_processes, 1),
            ("role", cfg.role, "both")) if value != plain]
        if asked:
            raise ValueError(
                f"model {self.mcfg.name!r} keeps {self.geom.one_chip_only}, "
                f"which serves on one unsharded chip only: {', '.join(asked)} "
                "is not supported (no sharding rule for it, no handoff of it "
                "to another engine)")

    def describe(self) -> dict[str, Any]:
        """What this engine bound and resolved — the start-up log line and
        /health carry it, so a caller that must stay off JAX (one process
        per chip) can still name the device and see which paths are live."""
        dev = self.device
        return {
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices()), "id": dev.id,
                       # Where the chip sits in its slice (TPU only).
                       "coords": getattr(dev, "coords", None)},
            "settings": {
                "model": self.mcfg.name, "n_layers": self.mcfg.n_layers,
                "dtype": self.mcfg.dtype,
                "max_batch": self.cfg.max_batch,
                "max_model_len": self.cfg.max_model_len,
                "kv_blocks": self.n_blocks,
                # What the cache holds and the family's forms: each says
                # its own (every key on every engine).
                **self.geom.describe(), **self.bound.describe(),
                "prefix_caching": isinstance(self.allocator,
                                             PrefixCachingAllocator),
                "decode_chunk": self.cfg.decode_chunk,
                "pallas_attention": bool(self.cfg.pallas_attention),
                "kv_wire": ("device" if self.kv_transfer_server is not None
                            else "host"),
                "kv_wire_error": self.kv_transfer_error,
                "compile_cache_dir": jax.config.jax_compilation_cache_dir,
                "weight_layouts": dict(self.weight_layouts),
            },
            "decode_kernel_in_program": dict(self.decode_kernel_in_program),
            "kv_imports": {"device": self.kv_import_device_count,
                           "host": self.kv_import_host_count,
                           "device_error": self.kv_import_device_error},
            "memory": {k: v for k, v in (dev.memory_stats() or {}).items()
                       if k in ("bytes_in_use", "peak_bytes_in_use",
                                "bytes_limit")},
        }

    # ---- jitted bodies -------------------------------------------------

    def _param_formats(self, params):
        """The formats (``jax.experimental.layout``) a one-chip engine holds
        ``params`` (arrays, or their shapes) in on a TPU, where the family
        names stacked weights whose layout is the decode program's to choose
        (``LAID_BY_DECODE``; models/llama.py has why); None wherever they
        stay as they come. The compiler is asked, not told: the decode chunk
        of every lane is compiled once from shapes alone, with
        ``Layout.AUTO`` on those weights, and what it settles on is what
        they are moved into (_laid_out): one copy of each, in the order of
        axes its layer loop reads. Every other program is then built for
        the arrays as they lie (jit takes a committed argument's layout),
        and every decode bucket's as well: this compile is nobody's
        program, and a warm start finds it in the compile cache (it takes
        its arguments in those layouts and returns none in one: see
        _laid_out)."""
        names = getattr(self.model, "LAID_BY_DECODE", ())
        if (not names or self.device.platform != "tpu"
                or self.mesh is not None or self.pp_mesh is not None):
            return None
        from jax.experimental.layout import Format, Layout
        from jax.sharding import SingleDeviceSharding

        here = SingleDeviceSharding(self.device)

        def shape(*dims, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=here)

        def shapes(tree):
            return jax.tree.map(
                lambda a: shape(*a.shape, dtype=a.dtype), tree)

        lanes = self._batch_bucket(self.cfg.max_batch)
        width = self.max_blocks_per_seq
        k_pages, v_pages = jax.eval_shape(
            functools.partial(pages.alloc, self.geom))
        cache = state_pool.at_slots(
            k_pages, np.zeros((lanes,), np.int32),
            np.zeros((lanes, width), np.int32) if self.geom.window else None,
            reads=self.bound.decode_expert_visits(lanes) > 0)
        args = shapes((
            params, shape(lanes), shape(lanes), cache, v_pages,
            shape(lanes, width), jax.eval_shape(lambda: jax.random.key(0)),
            shape(lanes, dtype=jnp.float32), shape(lanes),
            shape(lanes, dtype=jnp.float32), shape()))
        asked = jax.tree_util.tree_map_with_path(
            lambda path, _: Format(
                Layout.AUTO if path[-1].key in names else None, here),
            args[0])
        return jax.jit(
            self._decode_chunk_impl, donate_argnums=(3, 4),
            in_shardings=(asked, *(None,) * (len(args) - 1))).lower(
                *args).compile().input_formats[0][0]

    def _laid_out(self, params):
        """``params``, on the device, with the weights _param_formats speaks
        of moved into the layout it says, one at a time and each in place of
        the array it was (the rest, and every engine it says nothing to, as
        they come): no second copy of the weights is kept, and the largest
        of them is what a move needs beside them, before the pools are made.
        A move is a copy program compiled in a fraction of a second, OUTSIDE
        the compile cache: an executable whose result has a layout of its
        own comes back from the cache writing that layout into an array
        labelled with the default one (utils/compile_cache.py), which is
        also why the weights are not drawn in their layout by the program
        that makes them. /health says how they lie (``weight_layouts``),
        read off the arrays."""
        formats = self._param_formats(params)
        if formats is None:
            return params
        from ..utils.compile_cache import outside_compile_cache

        layers = dict(params["layers"])
        with outside_compile_cache():
            for name in self.model.LAID_BY_DECODE:
                want = formats["layers"][name]
                order = want.layout.major_to_minor
                if layers[name].format.layout.major_to_minor != order:
                    layers[name] = jax.device_put(layers[name], want,
                                                  donate=True)
                held = layers[name].format.layout.major_to_minor
                if held != order:       # its rows would be out of order
                    raise RuntimeError(
                        f"{name} was asked into {want.layout} and says it "
                        f"lies in {layers[name].format.layout}")
                self.weight_layouts[name] = list(held)
        return {**params, "layers": layers}

    def _decode_chunk_impl(self, params, tokens, positions, k_pages, v_pages,
                           block_tables, key, temps, top_k, top_p, n_steps):
        """``n_steps`` fused decode+sample steps in ONE dispatch, of at most
        ``decode_chunk``: the count is an operand, so a bucket has one
        program whatever lengths the loop asks for (_chunk_steps).

        A loop on device: each step runs the paged decode step and samples
        the next token, which feeds the following step. Returns the sampled
        tokens [K, B], rows past ``n_steps`` nobody's (step i draws with
        ``split(key, K)[i]`` whatever the count, so a chunk of K steps gives
        what the ``lax.scan`` it was gave); the host applies them per-lane
        up to each request's stop condition and discards the overshoot. A
        lane that ends on a stop token or an abort has the next chunk in
        flight already (_step), so the overshoot reaches up to 2K - 1
        positions past the request's end. Its KV writes land in the
        sequence's own allocated tail, or past it in the table's padding
        (the trash block) — never in a block of another live request, and
        never in a block the prefix cache holds (those are whole blocks of
        the prompt, below the first decoded position). Whatever reuses the
        freed blocks is dispatched later on the same in-order device stream
        and overwrites them. The input
        tokens come from the device (_slot_tokens), so the host neither
        reads nor books a chunk before it dispatches the next: one dispatch
        a chunk, and no idle device between chunks (PERF.md section 6, PR
        33, has what that is worth on the chip)."""
        K = self.cfg.decode_chunk
        keys = jax.random.split(key, K)

        def step(i, carry):
            tokens, positions, k_pages, v_pages, toks = carry
            logits, k_pages, v_pages = self.model.decode_step(
                params, self.bound.model_for(tokens.size), tokens, positions,
                k_pages, v_pages, block_tables,
                attention_fn=self._decode_attention)
            with scopes.block("sample"):
                nxt = sample_tokens(logits, keys[i], temps, top_k, top_p)
            return (nxt, positions + 1, k_pages, v_pages,
                    toks.at[i].set(nxt))

        *_, k_pages, v_pages, toks = jax.lax.fori_loop(
            0, n_steps, step, (tokens, positions, k_pages, v_pages,
                               jnp.zeros((K, tokens.size), tokens.dtype)))
        return toks, k_pages, v_pages

    def _prefill_fn(self, bucket: int, mm_bucket: int | None = None):
        """Per-bucket jitted prefill: forward + KV scatter + fused first-token
        sample (one dispatch covers prefill AND the first token — no separate
        sampler round-trip on the TTFT path). With ``mm_bucket`` the program
        takes two more operands behind seq_len (E/P/D phase 2): encoder
        vectors that overwrite the placeholder-token embeddings at their
        positions; padding entries point out of range and are dropped by
        the scatter."""
        mm = mm_bucket is not None
        name = (f"mm_prefill_b{bucket}_m{mm_bucket}" if mm
                else f"prefill_b{bucket}")
        fn_key = ("mm", bucket, mm_bucket) if mm else bucket
        if fn_key not in self._prefill_fns and self.pp_mesh is not None:
            from ..parallel.pp_serve import make_pp_prefill

            self._prefill_fns[fn_key] = make_pp_prefill(
                self.mcfg, self.pp_mesh, bucket, mm=mm)
        if fn_key not in self._prefill_fns:
            def impl(params, tokens, seq_len, *rest):
                (*mm_ops, k_pages, v_pages, block_table_row,
                 key, temps, top_k, top_p) = rest
                logits, (k_new, v_new) = self.model.forward(
                    params, self.bound.model_for(tokens.size), tokens,
                    want_kv=True, **(
                        dict(zip(("mm_embeds", "mm_positions"), mm_ops))
                        if mm else {"seq_len": seq_len}))
                with scopes.block("kv.write"):
                    k_pages, v_pages = pages.write_sequences(
                        k_pages, v_pages, k_new, v_new, block_table_row,
                        seq_len)
                with scopes.block("head"):
                    last = jnp.take_along_axis(
                        logits, (seq_len - 1)[:, None, None], axis=1)[:, 0]
                with scopes.block("sample"):
                    tok = sample_tokens(last, key, temps, top_k, top_p)
                return tok, k_pages, v_pages
            self._prefill_fns[fn_key] = jax.jit(
                _named(impl, name),
                donate_argnums=(5, 6) if mm else (3, 4))
        return self._prefill_fns[fn_key]

    def _prefix_prefill_fn(self, suffix_bucket: int, prefix_bucket: int):
        """Jitted prefill continuing from cached prefix KV, keyed on
        (suffix, prefix) pow2 buckets so a hit costs O(prefix)."""
        key = ("prefix", suffix_bucket, prefix_bucket)
        if key not in self._prefill_fns and self.pp_mesh is not None:
            from ..parallel.pp_serve import make_pp_prefill_with_prefix

            self._prefill_fns[key] = make_pp_prefill_with_prefix(
                self.mcfg, self.pp_mesh, suffix_bucket, prefix_bucket)
        if key not in self._prefill_fns:
            def impl(params, tokens, suffix_len, prefix_len, k_pages, v_pages,
                     block_table_row, prior_table_row,
                     rng, temps, top_k, top_p):
                logits, k_pages, v_pages = self.model.prefill_with_prefix(
                    params, self.bound.model_for(tokens.size), tokens,
                    suffix_len, prefix_len,
                    k_pages, v_pages, block_table_row, prior_table_row)
                with scopes.block("sample"):
                    tok = sample_tokens(logits, rng, temps, top_k, top_p)
                return tok, k_pages, v_pages
            self._prefill_fns[key] = jax.jit(
                _named(impl, f"prefix_prefill_s{suffix_bucket}_p{prefix_bucket}"),
                donate_argnums=(4, 5))
        return self._prefill_fns[key]

    # ---- public API (event-loop side) ---------------------------------

    async def start(self):
        self._thread = threading.Thread(target=self._run, name="tpu-engine", daemon=True)
        self._thread.start()

    async def stop(self):
        with self._cond:
            self._stop = True
            self._cond.notify()
        if self._thread:
            self._thread.join(timeout=10)
        if self._instr_channel is not None and self._instr_channel.leader:
            try:
                self._instr_channel.broadcast(("stop",), {})
            except Exception:
                log.exception("failed to release followers")
            self._instr_channel.close()
        if self.kv_events is not None:
            self.kv_events.close()
        if self.kv_shard_wire is not None:
            self.kv_shard_wire.close()

    def idle(self) -> bool:
        """True when nothing is admitted, queued, importing, fetching, or
        waiting on an embed — the SIGTERM drain gate (server.run_server).
        Kept here beside the state it reads so it cannot drift from the
        engine loop's own wake predicate."""
        with self._cond:
            busy = (any(s is not None for s in self.slots)
                    or self._waiting or self._import_ready
                    or self._embed_reqs or self._kv_fetching != 0
                    or self._release_reqs)
        if busy:
            return False
        # Staged P/D exports pin device KV a decode peer may still be
        # mid-pull on: draining a prefill pod while kv_exports
        # is non-empty (or releases are queued but not yet broadcast) would
        # tear the pages out from under the peer. Checked outside _cond —
        # no other path nests these locks in this order.
        with self._exports_lock:
            return not self.kv_exports

    def submit(self, req: EngineRequest) -> asyncio.Queue:
        """Thread-safe enqueue; returns the per-request event queue."""
        if self.geom.one_chip_only and (req.kv_transfer_params
                                        or req.mm_embeds is not None):
            raise ValueError(
                f"model {self.mcfg.name!r} keeps {self.geom.one_chip_only}: "
                "KV handoff to or from another engine and multimodal "
                "embeddings are not supported")
        out: asyncio.Queue = asyncio.Queue()
        loop = asyncio.get_running_loop()
        with self._cond:
            self._queue_submit[req.request_id] = time.monotonic()
            # Cap the stamp map: aborted/drained entries never reach the
            # admit-side pop, so trim oldest-first on the way in.
            while len(self._queue_submit) > 2048:
                self._queue_submit.pop(next(iter(self._queue_submit)))
            self._waiting.append((req, out, loop))
            self.telemetry.waiting.set(len(self._waiting))
            self._cond.notify()
        return out

    def abort(self, request_id: str) -> None:
        """Thread-safe abort: stops decode and frees blocks for the request."""
        with self._cond:
            self._abort_ids.add(request_id)
            self._cond.notify()

    def _transfer_address(self) -> str:
        """Advertised pull address: the server binds wildcard; peers dial the
        engine host."""
        port = self.kv_transfer_server.address().rsplit(":", 1)[1]
        return f"{self.cfg.host}:{port}"

    def _transfer_conn(self, address: str):
        with self._transfer_lock:
            conn = self._transfer_conns.get(address)
            if conn is None:
                conn = self.kv_transfer_server.connect(address)
                self._transfer_conns[address] = conn
            return conn

    def release_kv_export(self, request_id: str, *,
                          consumed: str = "host") -> None:
        """Drop a staged P/D export once the decode side has pulled it.

        ``consumed`` says HOW it was taken: "device" means the transfer-server
        registration was already drained by the peer's pull; anything else
        leaves the registration outstanding, so it is self-drained here (the
        transfer API has no cancel — the server otherwise holds the staged
        device arrays forever).

        Multi-host: every process registered its own shards, so the release
        must reach every process — it is queued here (callers run on the
        HTTP event loop or the engine thread) and broadcast as a
        release_kv_export op by the engine loop."""
        if self._dist:
            with self._cond:
                self._release_reqs.append((request_id, consumed))
                self._cond.notify()
            return
        self._release_export_local(request_id, consumed)

    def _release_export_local(self, request_id: str, consumed: str) -> None:
        with self._exports_lock:
            rec = self.kv_exports.pop(request_id, None)
        if rec is None:
            return
        if self.kv_shard_wire is not None and rec.get("shard_wire_uuid") is not None:
            self.kv_shard_wire.unregister(rec["shard_wire_uuid"])
        if consumed != "device":
            self._drain_staged_transfer(rec)

    def _drain_staged_transfer(self, rec: dict[str, Any]) -> None:
        """Self-pull an un-pulled staged uuid to release the transfer
        server's reference (loopback device copy; rare path)."""
        tuid = rec.get("transfer_uuid")
        shards = rec.get("staged_shards")
        if tuid is None or not shards or self.kv_transfer_server is None:
            return

        def drain():
            try:
                from jax.sharding import SingleDeviceSharding

                sds = [jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=SingleDeviceSharding(list(a.devices())[0]))
                    for a in shards]
                conn = self._transfer_conn(self._transfer_address())
                conn.pull(int(tuid), sds)
            except Exception:
                log.debug("staged-transfer drain failed", exc_info=True)

        # Own (daemon) thread: a drain of an already-pulled uuid would block
        # forever — only reachable if the peer pulled but its release signal
        # was lost, which leaks one idle thread, not device memory.
        threading.Thread(target=drain, name="kv-drain", daemon=True).start()

    def _page_mesh(self):
        """The mesh the page buffers are sharded over (kvcache/pages.py has
        the rule); None when the engine is single-device."""
        return self.pp_mesh if self.pp_mesh is not None else self.mesh

    def get_kv_export(self, request_id: str) -> dict[str, Any] | None:
        with self._exports_lock:
            return self.kv_exports.get(request_id)

    # ---- engine thread -------------------------------------------------

    def _emit(self, slot: _Slot, ev: TokenEvent):
        slot.loop.call_soon_threadsafe(slot.out.put_nowait, ev)

    def _emit_to(self, out, loop, ev: TokenEvent):
        loop.call_soon_threadsafe(out.put_nowait, ev)

    def _bucket(self, n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return min(b, self.cfg.max_model_len)

    def embed(self, ids: list[int]) -> np.ndarray:
        """Mean-pooled final-hidden-state embedding of a prompt — the
        /v1/embeddings surface (the reference routes OpenAI embeddings
        bodies to vLLM embedding pods; this is the engine-half equivalent).

        Stateless w.r.t. the batching loop (no KV pages/slots touched).
        Pow2 prompt buckets bound the compile cache. Padding tokens sit
        AFTER the valid prompt, so causal attention never lets a valid
        query attend them; the mask excludes them from the mean.
        Single-process engines (plain / tp / pp rings) dispatch directly
        from the caller's thread; multi-host engines must issue every
        device op in broadcast order, so the request queues to the engine
        thread and replays on the followers like any other op."""
        bucket = self._bucket(max(len(ids), 1))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, : len(ids)] = ids
        seq_len = np.asarray([max(len(ids), 1)], np.int32)
        if self._dist:
            import concurrent.futures

            fut: concurrent.futures.Future = concurrent.futures.Future()
            with self._cond:
                if self.dist_degraded or self._stop:
                    raise ValueError("engine unavailable for embeddings "
                                     "(degraded or stopping)")
                self._embed_reqs.append((bucket, tokens, seq_len, fut))
                self._cond.notify()
            return fut.result(timeout=600.0)
        return self._op_embed(bucket, tokens=tokens, seq_len=seq_len)

    def _op_embed(self, bucket: int, *, tokens, seq_len) -> np.ndarray:
        fn = self._embed_fn_for(bucket)
        vec = fn(self.params, self._put(tokens), self._put(seq_len))
        return np.asarray(vec)

    def _embed_fn_for(self, bucket: int):
        # Lock the per-bucket fn creation: two concurrent first calls would
        # otherwise each build+compile their own jit (benign race, duplicated
        # compile work). Sharing one fn lets jax's own dispatch
        # cache dedup the compilation.
        with self._embed_fns_lock:
            fn = self._embed_fns.get(bucket)
            if fn is None:
                if self.pp_mesh is not None:
                    from ..parallel.pp_serve import make_pp_embed

                    fn = make_pp_embed(self.mcfg, self.pp_mesh, bucket)
                else:
                    def impl(params, tokens, seq_len):
                        hidden, _ = self.model.forward(
                            params, self.bound.model_for(tokens.size), tokens,
                            want_hidden=True)
                        mask = (jnp.arange(tokens.shape[1])
                                < seq_len[0])[None, :, None]
                        pooled = (hidden * mask).sum(axis=1) / seq_len[0]
                        return pooled[0]

                    _named(impl, f"embed_b{bucket}")
                    if self._dist:
                        from jax.sharding import NamedSharding, PartitionSpec

                        # Replicated output: every process must hold an
                        # addressable copy of the vector.
                        fn = jax.jit(impl, out_shardings=NamedSharding(
                            self.mesh, PartitionSpec()))
                    else:
                        fn = jax.jit(impl)
                self._embed_fns[bucket] = fn
        return fn

    def _warmup(self):
        """Compile the hot jits before serving (smallest prefill bucket,
        decode step, sampler) — all writes land in the trash block."""
        t0 = time.monotonic()
        B = self.cfg.max_batch
        nobody = np.full((1,), B, np.int32)  # no slot keeps a warm-up token
        bucket = self._bucket(16)  # respects max_model_len < 16
        self._device_call(("prefill", bucket), dict(
            tokens=np.zeros((1, bucket), np.int32),
            seq_len=np.asarray([1], np.int32),
            row=np.zeros((1, self.max_blocks_per_seq), np.int32),
            slots=nobody, warm=True, **self._window_tables([], 1),
            **self._sample_np([_DUMMY_REQ])))
        if self._prefill_window():
            # Incremental prefill's mid-stream shapes: every intermediate
            # window is FULL-width, so precompiling (win_bucket × pb ladder)
            # removes the per-shape compile stall the feature exists to
            # avoid. Only the final ragged window of a novel length may
            # still lazy-compile once.
            win = self._prefill_window()
            wb = self._bucket(win)
            self._device_call(("prefill", wb), dict(
                tokens=np.zeros((1, wb), np.int32),
                seq_len=np.asarray([1], np.int32),
                row=np.zeros((1, self.max_blocks_per_seq), np.int32),
                slots=nobody, warm=True, **self._window_tables([], 1),
                **self._sample_np([_DUMMY_REQ])))
            pb = 1
            while True:
                self._device_call(("prefix_prefill", wb, pb), dict(
                    tokens=np.zeros((1, wb), np.int32),
                    suffix_len=np.asarray([1], np.int32),
                    prefix_len=np.asarray([0], np.int32),
                    row=np.zeros((1, self.max_blocks_per_seq), np.int32),
                    prior=np.zeros((1, pb), np.int32),
                    slots=nobody, warm=True, **self._window_tables([], 1),
                    **self._sample_np([_DUMMY_REQ])))
                if pb >= self.max_blocks_per_seq:
                    break
                pb = min(pb * 2, self.max_blocks_per_seq)
        # Compile EVERY decode bucket _batch_bucket can produce (2, 4, …,
        # max_batch): a gate-able warm-up must leave no lazy compile to stall
        # the engine thread mid-serving.
        for nb in sorted({self._batch_bucket(n) for n in range(1, B + 1)}):
            self._device_call(("decode",), dict(
                slots=np.full((nb,), B, np.int32),
                positions=np.zeros((nb,), np.int32),
                tables=np.zeros((nb, self.max_blocks_per_seq), np.int32),
                steps=self.cfg.decode_chunk, warm=True,
                **self._window_tables([], nb),
                **self._sample_np([_DUMMY_REQ] * nb)))
        log.info("engine warm-up compiled prefill/decode/sample in %.1fs",
                 time.monotonic() - t0)

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One phase of the engine loop, both ways at once so the two cannot
        drift: a host span `engine.<name>` in the profiler's trace (inert
        while no trace runs) and the same interval added to
        jetstream:engine_loop_seconds_total{phase}. Phases never nest."""
        t0 = self._clock()
        try:
            with jax.profiler.TraceAnnotation("engine." + name):
                yield
        finally:
            dt = self._clock() - t0
            self.telemetry.loop_seconds[name].inc(dt)
            self._period[name] += dt

    def _begin_period(self) -> None:
        self._period = dict.fromkeys(LOOP_PHASES, 0.0)
        self._period_prefills = 0
        self._period_first_call = False

    def _run(self):
        if self.kv_events is not None:
            # Bind BEFORE warm-up: subscribers join during the compile window.
            try:
                # Bind here so the PUB socket lives on the thread that uses it
                # AND subscribers can join long before the first real event.
                self.kv_events.bind_now()
            except Exception:
                log.exception("kv event publisher bind failed; disabled")
                self.kv_events = None
        if self.cfg.warmup:
            try:
                self._warmup()
            except Exception as e:
                # A program the compiler refuses, or that does not fit the
                # device, shows itself here first. Serving cold would hide
                # it until a request meets the same error: stop instead.
                # /health reports it and run_server exits non-zero.
                log.critical("engine warm-up failed; engine stopped",
                             exc_info=True)
                self.fatal = e
                return
        self.warming = False
        while True:
            with self._cond:
                while (not self._stop and not self._waiting and not self._import_ready
                       and not self._abort_ids and not self._embed_reqs
                       and not any(self.slots) and self._inflight is None):
                    with self._phase("idle_wait"):
                        self._cond.wait(timeout=0.1)
                    # Keep the 1s KV snapshot cadence alive while idle: a
                    # subscriber joining an idle-but-warm engine must still
                    # learn its cache contents (PUB/SSE have no replay).
                    self._publish_kv_snapshot()
                if self._stop:
                    for *_, fut in self._embed_reqs:
                        fut.set_exception(ValueError("engine stopping"))
                    self._embed_reqs = []
                    self._inflight = None   # nobody will read it
                    return
            if self.dist_degraded:
                # Drain everything (queued work included) without touching
                # the device — any collective would hang on the dead peer.
                self._abort_all("multi-host peer lost")
                continue
            try:
                self._step()
            except ChannelBroken:
                log.error("instruction channel broken; degrading")
                self.dist_degraded = True
            except Exception:
                log.exception("engine loop failure; aborting in-flight requests")
                self._abort_all("engine loop failure")

    def _step(self):
        with self._phase("housekeeping"):
            self._drain_release_reqs()
            self._drain_embed_reqs()
            self._sweep_exports()
            self._publish_kv_snapshot()
            self._process_aborts()
            self._process_imports()
        at = "step"
        while True:
            with self._phase("admit"):
                self._admit(at)
            with self._phase("advance_prefills"):
                self._advance_prefills()
            if not self._hold_for_arrival():
                break
            at = "hold"     # woken by an arrival: placed now, and held on
        # The next chunk goes out BEFORE the one in flight is read: it queues
        # on the device behind that chunk and behind the prefills above, and
        # takes its tokens from the device (theirs, _slot_tokens), so the
        # device has work while the host reads and books. Then the chunk
        # before is read and booked, and last the prefills' first tokens
        # land: one chunk period ahead of their tokens 2 to K + 1.
        landing = self._inflight
        self._inflight = self._dispatch_chunk()
        if landing is not None:
            self._land_chunk(landing)
        self._finalize_prefills()
        if landing is None and not any(self.slots):
            with self._cond:
                if (self._waiting or self._import_ready) and not self._abort_ids:
                    # Head-of-line can't be placed yet (no free blocks / no slot
                    # / fetch in flight): sleep until something changes.
                    with self._phase("idle_wait"):
                        self._cond.wait(timeout=0.05)

    def _other_work(self) -> bool:
        """Whether anything but a request has come in for the loop to do;
        under _cond."""
        return bool(self._abort_ids or self._import_ready or self._embed_reqs
                    or self._release_reqs or self._stop or self.dist_degraded)

    def _work_arrived(self) -> bool:
        return bool(self._waiting) or self._other_work()

    def _open_slots(self) -> tuple[list[int], list[int]]:
        """The slots a request can be admitted into: the empty ones, and
        those whose request ends inside the chunk in flight (on max_tokens
        or the context limit: the next chunk leaves its lane out already)."""
        empty = [i for i, s in enumerate(self.slots) if s is None]
        vacating = [i for i, s in enumerate(self.slots) if s is not None
                    and s.ahead and self._ends_in_flight(s)]
        return empty, vacating

    def _room_for_arrival(self) -> bool:
        """Whether a request that arrived now would be placed at once, which
        is what holding a chunk back (_hold_until) and cutting it short
        (_chunk_steps) are both for: a slot is open, nobody waits for it
        (nor anything else for the loop), and no slot has windows still to
        write."""
        if any(s is not None and s.prefilling for s in self.slots):
            return False
        with self._cond:
            if self._work_arrived():
                return False
        return any(self._open_slots())

    def _chunk_time(self, shape: str, steps: int) -> float | None:
        """What a chunk of this shape and length is reckoned to take on the
        device: the least of its last clean periods; pro rata from the same
        shape at another length until it has one of its own (a shorter
        chunk bears the chunk's fixed costs too, so reckoned from a longer
        one it reads early, the way the hold errs); None where the shape was
        never timed."""
        times = self._chunk_times.get((shape, steps))
        if times:
            return min(times)
        return next((min(times) * steps / n for (sh, n), times
                     in self._chunk_times.items() if sh == shape and times),
                    None)

    def _chunk_steps(self, shape: str) -> int:
        """How many steps the chunk now going out runs: decode_chunk, or a
        short length where an arrival could be placed at once
        (_room_for_arrival) and its prefill would go ahead of the chunk
        after this one: it then waits out the rest of a chunk a quarter as
        long, or half. A short chunk pays the chunk's fixed costs that much
        more often, and the loop must still do its own work a chunk inside
        it with KEEP_UP_S to spare, or the device's queue runs empty: the
        length is the shortest of SHORT_CHUNK_DIVS' that what the loop
        measured of itself over the last periods fits in, so a host too slow
        for a quarter falls to a half and not to a whole chunk; where it
        fits neither, and wherever an arrival could not be placed sooner
        anyway (no open slot, a queue, windows being written), the chunk is
        full. (Whether the NEXT chunk can then be held back is _hold_until's
        own question, asked with its own margin.) A pp engine's program is K
        steps."""
        full = self.cfg.decode_chunk
        if (self.pp_mesh is not None or not self._host_work
                or not self._room_for_arrival()):
            return full
        host = sum(self._host_work) / len(self._host_work)
        for div in SHORT_CHUNK_DIVS.values():       # (the shortest first)
            steps = full // div
            reckoned = steps and self._chunk_time(shape, steps)
            if reckoned and host + KEEP_UP_S <= reckoned:
                return steps
        return full

    def _hold_until(self) -> float | None:
        """Until when, on the loop's clock, the next chunk can be held back
        for an arrival; None where it goes out now. It can where the device
        has a chunk to work on whose end the loop can reckon (it has timed
        that shape with nothing ahead of it) and an arrival would be placed
        at once (_room_for_arrival). The chunk in flight
        started no sooner than it was dispatched, than the chunk before it
        was read, and than the first tokens of the prefills ahead of it
        were; what else sits ahead of it makes it end later than reckoned,
        and the next chunk is then early, which costs an arrival its place
        and the device nothing."""
        chunk = self._inflight
        reckoned = chunk and self._chunk_time(chunk.shape, chunk.steps)
        if not reckoned or not self._room_for_arrival():
            return None
        until = (max(chunk.t0, self._last_readback, self._first_tokens_read)
                 + reckoned - HOLD_MARGIN_S)
        return until if until > self._clock() else None

    def _await_work(self, until: float) -> bool:
        """Sleep until something comes in for the loop to do (submit(),
        abort() and the rest notify _cond) or its clock reads `until`;
        whether something has."""
        with self._cond:
            return self._cond.wait_for(
                self._work_arrived, timeout=max(until - self._clock(), 0.0))

    def _hold_for_arrival(self) -> bool:
        """Hold the next chunk back until shortly before the one in flight
        ends, where _hold_until says it can be; whether an arrival, and
        nothing else, ended the hold sooner (_step places it and holds on).
        Dispatched now, a whole chunk period before the device needs it, the
        chunk would stand in the device's queue ahead of the prefill of
        every request that arrives in that period: a first token then waits
        out a chunk it has no part in. Held, the loop sleeps on _cond where
        it would have slept inside the readback; an arrival wakes it and is
        admitted at once, its prefill behind the running chunk alone and its
        lane in the next. The sleep is time the device works and the loop
        does not: decode_wait's."""
        until = self._hold_until()
        if until is None:
            return False
        with self._phase("decode_wait"):
            self._await_work(until)
        with self._cond:
            return bool(self._waiting) and not self._other_work()

    def _on_follower_lost(self, idx: int, why: str) -> None:
        """Peer-monitor callback (runs on the channel's watch thread)."""
        log.error("follower %d lost (%s): engine degrading — coordinated "
                  "restart required", idx, why)
        self.dist_degraded = True
        with self._cond:
            self._cond.notify()

    def _abort_all(self, reason: str):
        self._inflight = None   # its lanes end here; nobody will read it
        for i, s in [*enumerate(self.slots), *self._retired]:
            if s is not None:
                self._finish_slot(i, s, FinishReason.ABORT)
        with self._cond:
            drained, self._waiting = self._waiting, []
            self.telemetry.waiting.set(0)
            imports, self._import_ready = self._import_ready, []
            embeds, self._embed_reqs = self._embed_reqs, []
        for *_, fut in embeds:
            if not fut.done():
                fut.set_exception(ValueError(f"engine aborted: {reason}"))
        for req, out, loop in drained:
            self._emit_to(out, loop, TokenEvent(
                request_id=req.request_id, token_id=None,
                finish_reason=FinishReason.ABORT,
                prompt_tokens=len(req.prompt_token_ids)))
        for pi in imports:
            self._emit_to(pi.out, pi.loop, TokenEvent(
                request_id=pi.req.request_id, token_id=None,
                finish_reason=FinishReason.ABORT,
                prompt_tokens=len(pi.req.prompt_token_ids)))

    def _publish_kv_snapshot(self):
        """Periodically re-publish the block hashes of live slots.

        ZMQ PUB/SUB has no retransmit: a `stored` event published before a
        late-joining subscriber finishes its handshake is lost forever. The
        snapshot (idempotent `stored` adds, 1s cadence) guarantees the
        router's index converges regardless of join timing — the analogue of
        the reference engines' continuous event stream.
        """
        if self.kv_events is None:
            return
        now = time.monotonic()
        if now - self._last_kv_snapshot < 1.0:
            return
        self._last_kv_snapshot = now
        if isinstance(self.allocator, PrefixCachingAllocator):
            # With prefix caching the content-addressed map IS the cache state
            # (active + parked reusable blocks).
            hashes = self.allocator.cached_hashes()
        else:
            hashes = [h for s in self.slots if s is not None
                      for h in s.block_hashes]
        if hashes:
            self.kv_events.stored(hashes)

    def _drain_release_reqs(self):
        """Multi-host release fan-out: queued by release_kv_export (HTTP
        event loop / sweep), broadcast here so every process drops its own
        shard registrations in op order."""
        with self._cond:
            reqs, self._release_reqs = self._release_reqs, []
        for rid, consumed in reqs:
            self._device_call(("release_kv_export",),
                              dict(request_id=rid, consumed=consumed))

    def _drain_embed_reqs(self):
        """Multi-host embeddings: run queued embed ops on the engine thread
        (broadcast order is the lockstep contract — a second thread issuing
        device ops would interleave with decode ops on the followers)."""
        with self._cond:
            reqs, self._embed_reqs = self._embed_reqs, []
        for i, (bucket, tokens, seq_len, fut) in enumerate(reqs):
            try:
                fut.set_result(self._device_call(
                    ("embed", bucket), dict(tokens=tokens, seq_len=seq_len)))
            except ChannelBroken:
                # Lockstep is over: fail EVERY popped request (they are no
                # longer on the queue, so the degrade drain can't reach
                # them), then let the loop degrade.
                for _, _, _, f in reqs[i:]:
                    if not f.done():
                        f.set_exception(ValueError(
                            "engine degraded (multi-host peer lost)"))
                raise
            except Exception as e:
                fut.set_exception(e)

    def _sweep_exports(self):
        now = time.monotonic()
        with self._exports_lock:
            expired = [(rid, rec) for rid, rec in self.kv_exports.items()
                       if now - rec["created"] > KV_EXPORT_TTL_S]
        if self._dist:
            # Followers must drop their shard registrations too: route the
            # expiry through the broadcast release op.
            for rid, _ in expired:
                log.warning("kv export %s expired unclaimed; dropping", rid)
                self._device_call(("release_kv_export",),
                                  dict(request_id=rid, consumed="expired"))
            return
        with self._exports_lock:
            for rid, _ in expired:
                log.warning("kv export %s expired unclaimed; dropping", rid)
                self.kv_exports.pop(rid, None)
        for _, rec in expired:
            # Unclaimed = never pulled: safe to self-drain the registration.
            self._drain_staged_transfer(rec)

    def _process_aborts(self):
        with self._cond:
            ids, self._abort_ids = self._abort_ids, set()
            if not ids:
                return
            keep = []
            for req, out, loop in self._waiting:
                if req.request_id in ids:
                    self._emit_to(out, loop, TokenEvent(
                        request_id=req.request_id, token_id=None,
                        finish_reason=FinishReason.ABORT,
                        prompt_tokens=len(req.prompt_token_ids)))
                else:
                    keep.append((req, out, loop))
            self._waiting = keep
            self.telemetry.waiting.set(len(self._waiting))
        for i, s in enumerate(self.slots):
            if s is not None and s.req.request_id in ids:
                self._finish_slot(i, s, FinishReason.ABORT)

    # ---- admission -----------------------------------------------------

    def _blocks_needed(self, req: EngineRequest) -> int:
        prompt_len = len(req.prompt_token_ids)
        total = min(prompt_len + req.max_tokens, self.cfg.max_model_len)
        need = self.allocator.blocks_for_tokens(total)
        ktp = req.kv_transfer_params or {}
        if ktp.get("remote_num_blocks"):
            need = max(need, int(ktp["remote_num_blocks"]))
        return need

    def _record_queue_wait(self, request_id: str) -> float | None:
        """Measure admission wait at the FIRST _admit pop (first-pop-wins:
        a KV-fetch re-insert finds its stamp already consumed and is not
        re-measured). The server pops the result for x-engine-queue-ms, a
        header only an unstreamed response can still carry. Returns the
        wait in seconds, None for a pop that is not the first."""
        t0 = self._queue_submit.pop(request_id, None)
        if t0 is None:
            return None
        wait_s = time.monotonic() - t0
        self.queue_waits[request_id] = wait_s * 1e3
        self._queue_wait_order.append(request_id)
        while len(self._queue_wait_order) > 512:
            self.queue_waits.pop(self._queue_wait_order.popleft(), None)
        return wait_s

    def _note_admission(self, req: EngineRequest) -> None:
        """A request leaves the waiting queue. At its first pop the wait goes
        into jetstream:queue_wait_seconds (every request, streamed or not)
        and admit -> first token starts."""
        wait_s = self._record_queue_wait(req.request_id)
        if wait_s is not None:
            req.admit_time = time.monotonic()
            self.telemetry.queue_wait.observe(wait_s)

    def _admit(self, at: str = "step"):
        """Place the head of the queue, slot by slot, while it fits (`at`
        the top of a step, or woken inside a `hold`: _hold_for_arrival). The
        empty slots first; then, for requests that still wait, the slots
        whose request ends inside the chunk in flight (on max_tokens or the
        context limit, _ends_in_flight: the next chunk leaves its lane out
        already). Such a slot's successor is prefilled now, behind that
        chunk in the device's queue, and decodes in the next one as the
        slot's lane, which would else be nobody's for a whole chunk. What a
        slot index names on the device (_slot_tokens, the state pool's row)
        is the successor's from its prefill on, by the queue's order alone;
        its pages are its own, the predecessor keeps its blocks until its
        last chunk is booked (_place, _book_chunk)."""
        empty, vacating = self._open_slots()
        for i in empty + vacating:
            when = "after" if self.slots[i] is None else "ahead"
            with self._cond:
                if not self._waiting:
                    break
                req, out, loop = self._waiting[0]
                ktp = req.kv_transfer_params or {}
                if when == "ahead" and (
                        ktp.get("remote_host") is not None
                        or ktp.get("do_remote_decode")
                        or req.mm_embeds is not None):
                    # An import is placed when its pages arrive, an export
                    # and an image prompt never had a lane to wait for: the
                    # head of the queue waits for an empty slot, as before.
                    break
                need = self._blocks_needed(req)
                if need > self.n_blocks - 1:
                    # Impossible request: reject instead of wedging the queue.
                    self._waiting.pop(0)
                    self.telemetry.waiting.set(len(self._waiting))
                    self._note_admission(req)
                    self._emit_to(out, loop, TokenEvent(
                        request_id=req.request_id, token_id=None,
                        finish_reason=FinishReason.ABORT,
                        prompt_tokens=len(req.prompt_token_ids)))
                    continue
                if ktp.get("remote_host") is not None:
                    # Fetch off-thread; the payload comes back via _import_ready.
                    self._waiting.pop(0)
                    self.telemetry.waiting.set(len(self._waiting))
                    self._note_admission(req)
                    self._start_kv_fetch(req, out, loop)
                    continue
                if need > getattr(self.allocator, "reusable_blocks",
                                  self.allocator.free_blocks):
                    break  # head-of-line waits for capacity
                self._waiting.pop(0)
                self.telemetry.waiting.set(len(self._waiting))
                self._note_admission(req)
                self.telemetry.slot_refills[when].inc()
                self.telemetry.admissions[at].inc()
            # A dispatch that fails has cleaned up after its own request and
            # raises; the requests behind it still wait.
            self._prefill_into_slot(i, req, out, loop, need)

    def _prompt_and_hashes(self, req):
        """Truncated prompt + content-hash chain + caching gate."""
        prompt = req.prompt_token_ids[: self.cfg.max_model_len - 1]
        if len(prompt) < len(req.prompt_token_ids):
            # Last-resort guard for direct submit() callers; the HTTP surface
            # rejects over-context prompts with 400 before reaching here.
            log.warning("request %s: prompt truncated %d -> %d tokens "
                        "(max_model_len %d)", req.request_id,
                        len(req.prompt_token_ids), len(prompt),
                        self.cfg.max_model_len)
        caching = isinstance(self.allocator, PrefixCachingAllocator)
        if req.mm_embeds is not None:
            # Multimodal prompts are NOT content-addressable by token ids:
            # identical placeholder tokens can carry different images, so
            # prefix caching and KV-event publication are disabled for them.
            caching = False
        hashes = (chain_block_hashes(self.model_name, prompt, "",
                                     self.mcfg.kv_block_size)
                  if caching or
                  (self.kv_events is not None and req.mm_embeds is None
                   and not (self.geom.state or self.geom.window))
                  else [])
        return prompt, hashes, caching

    def _window_tables(self, steps, rows: int) -> dict:
        """What rides with a program where some cache layers keep a window
        of the context (kvcache/pages.py): ``wt``, a second table a row,
        zeros but for what ``steps`` say, (a request's blocks, the positions
        [start, end) its row of the program writes) each; with ``ahead`` a
        program run once, a prefill window. The allocator slides each
        request's window pages to the step as it fills the row
        (engine/blocks.WindowedAllocator), and a decoding lane's row is
        counted by the groups the window layers' kernels fetch it in
        (kv_window_table_groups_total). Nothing for any other cache."""
        w = self.geom.window
        if w is None:
            return {}
        wt = np.zeros((rows, self.max_blocks_per_seq), np.int32)
        runs = splits = 0
        with self._cond:
            for lane, (blocks, start, end, ahead) in enumerate(steps):
                self.allocator.slide(blocks, start, end, wt[lane], ahead)
                if not ahead:
                    r, s = window_table_groups(wt[lane], start, w.block,
                                               w.window, self.geom.run_pages)
                    runs, splits = runs + r, splits + s
            self.telemetry.observe_allocator(self.allocator)
        self.telemetry.kv_window_table_groups["run"].inc(runs)
        self.telemetry.kv_window_table_groups["split"].inc(splits)
        return {"wt": wt}

    def _note_table(self, blocks: list[int]) -> None:
        """Count an admitted request's block table by the groups the decode
        kernels fetch it in (kv_table_groups_total)."""
        runs, splits = table_groups(blocks, self.geom.run_pages)
        self.telemetry.kv_table_groups["run"].inc(runs)
        self.telemetry.kv_table_groups["split"].inc(splits)

    # ---- prefill -------------------------------------------------------

    def _place(self, idx: int, slot: _Slot) -> None:
        """``slot`` takes engine slot idx. A request still there is one
        that _admit saw vacating: it is retired, served on through the
        lanes of the chunk in flight until that chunk is booked."""
        if self.slots[idx] is not None:
            self._retired.append((idx, self.slots[idx]))
        self.slots[idx] = slot
        self.telemetry.running.set(sum(s is not None for s in self.slots))

    def _prefill_into_slot(self, idx, req, out, loop, need: int):
        """Admit req into slot idx: its block table (the longest cached run
        of whole prompt blocks, then new ones) and the slot, parked
        PREFILLING with what is left of the prompt to write. A rest of one
        window is written here and now; a longer one a window a step
        (_advance_prefills)."""
        if (self._dist and self.kv_transfer_server is None
                and (req.kv_transfer_params or {}).get("do_remote_decode")):
            # Multi-host staging is shard-registered on every process's
            # transfer server (stage_kv op); without one there is no host
            # fallback either (global pages are not fully addressable), so
            # reject instead of staging an unclaimable export.
            log.warning("rejecting do_remote_decode request %s: no KV "
                        "transfer server in multi-host mode",
                        req.request_id)
            self._emit_to(out, loop, TokenEvent(
                request_id=req.request_id, token_id=None,
                finish_reason=FinishReason.ABORT,
                prompt_tokens=len(req.prompt_token_ids)))
            return
        block = self.mcfg.kv_block_size
        prompt, hashes, caching = self._prompt_and_hashes(req)

        # Automatic prefix caching: reuse the longest cached run of complete
        # prompt blocks (keeping ≥1 suffix token so logits can be computed).
        matched_bids: list[int] = []
        with self._cond:
            if caching and hashes:
                max_match = (len(prompt) - 1) // block
                matched_bids = self.allocator.match_prefix(hashes)[:max_match]

            # Shared-storage probe: bail out before any allocation when the
            # cache can't cover enough of the prompt (sidecar then runs the
            # remote prefill leg and retries). Ratio is over the MATCHABLE
            # prefix (complete blocks minus the mandatory suffix token), so a
            # fully warm cache always scores 1.0 even for block-aligned
            # prompts.
            if req.cache_hit_threshold is not None and prompt:
                max_match = (len(prompt) - 1) // block
                hit_ratio = (len(matched_bids) / max_match) if max_match else 1.0
                if hit_ratio < req.cache_hit_threshold:
                    self._note_prefix_hit(req.request_id,
                                          len(matched_bids) * block,
                                          len(prompt), kind="probe")
                    self._emit_to(out, loop, TokenEvent(
                        request_id=req.request_id, token_id=None,
                        finish_reason=FinishReason.CACHE_THRESHOLD,
                        prompt_tokens=len(prompt),
                        cached_tokens=len(matched_bids) * block))
                    self.telemetry.request_success.labels(
                        finished_reason=FinishReason.CACHE_THRESHOLD.value).inc()
                    return

            if caching and matched_bids:
                self.allocator.acquire_cached(matched_bids)
            new_bids = self.allocator.alloc(need - len(matched_bids))
            evicted = list(getattr(self.allocator, "last_evicted_hashes", []))
            # (Nothing matched: the table as the allocator handed it out,
            # with what rides on it.)
            blocks = matched_bids + new_bids if matched_bids else new_bids
            self.telemetry.observe_allocator(self.allocator)
        if evicted and self.kv_events is not None:
            self.kv_events.removed(evicted)
        self._note_table(blocks)

        cached_tokens = len(matched_bids) * block
        self._note_prefix_hit(req.request_id, cached_tokens, len(prompt))
        self.telemetry.prefix_cached_tokens.inc(cached_tokens)
        slot = _Slot(req=req, out=out, loop=loop, blocks=blocks,
                     position=len(prompt), generated=[],
                     cached_tokens=cached_tokens, prompt_len=len(prompt),
                     prefilling=True, prefill_rest=list(prompt[cached_tokens:]),
                     prefill_written=cached_tokens,
                     chunk_meta=(hashes, caching))
        self._place(idx, slot)
        if len(self._next_window(slot)) == len(slot.prefill_rest):
            self._write_prefill_window(idx)

    def _finalize_prefills(self):
        """Land pending first tokens and emit/finish accordingly. Reading
        them blocks until their prefills are done, with the chunk in flight
        queued behind those: a wait on a busy device, booked as decode_wait
        so that finalize_prefills stays host work."""
        pending = [(idx, slot) for idx, slot in enumerate(self.slots)
                   if slot is not None and slot.pending_tok is not None
                   and not slot.prefilling]
        if not pending:
            return
        with self._phase("decode_wait"):
            landed = [int(self._read_tokens(slot.pending_tok)[0])
                      for _, slot in pending]
        self._first_tokens_read = self._clock()
        self._period_prefills += len(pending)
        with self._phase("finalize_prefills"):
            for (idx, slot), tok in zip(pending, landed):
                slot.pending_tok = None
                slot.generated = [tok]
                req = slot.req
                self._observe_first_token(req)
                self.telemetry.generation_tokens.inc()

                # Remote-decode prefill: hand KV off instead of decoding here.
                ktp = req.kv_transfer_params or {}
                if ktp.get("do_remote_decode"):
                    self._finish_slot(idx, slot, FinishReason.LENGTH,
                                      retain_for_transfer=True, first_token=tok)
                    continue
                self._emit(slot, TokenEvent(
                    request_id=req.request_id, token_id=tok,
                    text=self.tokenizer.decode([tok]), is_first=True,
                    prompt_tokens=slot.prompt_len, completion_tokens=1,
                    cached_tokens=slot.cached_tokens))
                slot.first_emitted = True
                self._maybe_finish_after_token(idx, slot, tok)

    def _observe_first_token(self, req: EngineRequest) -> None:
        """A first token has landed on the host: TTFT from the request's
        construction, and the part of it since the admission pop."""
        now = time.monotonic()
        self.telemetry.ttft.observe(now - req.arrival_time)
        if req.admit_time is not None:
            self.telemetry.admit_to_first_token.observe(now - req.admit_time)

    def _prefill_window(self) -> int:
        """A prompt's window in tokens (a KV-block multiple so every
        intermediate boundary is block-aligned); 0 = the whole prompt."""
        w = self.cfg.prefill_chunk
        if w <= 0:
            return 0
        block = self.mcfg.kv_block_size
        return max(block, (w + block - 1) // block * block)

    def _next_window(self, s: "_Slot") -> list[int]:
        """The tokens s's next window writes: the rest of its prompt where
        no window size is set, and of an image prompt (the embed splice
        targets absolute positions in the first forward)."""
        win = self._prefill_window()
        if win and s.req.mm_embeds is None:
            return s.prefill_rest[:win]
        return s.prefill_rest

    def _maybe_stage_chunk(self, s: "_Slot") -> None:
        """Incremental KV staging for a chunk-streamed remote-decode
        prefill (``kv_transfer_params.stream_chunks``, single-device host
        path only — sharded/multi-host pages have no host-addressable chunk
        bytes, so those exports stage whole at completion and the decode
        peer's chunked pull degrades to the legacy full GET). Gathers the
        newly COMPLETE blocks to host and appends them to the request's
        ``kv_exports`` record; the record is created at the first chunk
        (``complete=False``) so the SIGTERM drain gate (idle()) pins the
        pod for the decode peer from the very first staged block."""
        ktp = s.req.kv_transfer_params or {}
        if not (ktp.get("do_remote_decode") and ktp.get("stream_chunks")):
            return
        if self._dist or self._page_mesh() is not None:
            return
        block = self.mcfg.kv_block_size
        rid = s.req.request_id
        with self._exports_lock:
            rec = self.kv_exports.get(rid)
        upto = min(s.prefill_written // block, len(s.blocks))
        staged = int(rec["blocks_staged"]) if rec is not None else 0
        if upto - staged <= 0:
            return
        if rec is None:
            rec = {"created": time.monotonic(), "seq_len": s.prompt_len,
                   "num_blocks": len(s.blocks), "chunk_data": [],
                   "chunk_blocks": [], "chunks_staged": 0,
                   "blocks_staged": 0, "complete": False}
            with self._exports_lock:
                self.kv_exports[rid] = rec
        k_np, v_np = (np.asarray(a) for a in pages.gather_blocks(
            self.k_pages, self.v_pages,
            np.asarray(s.blocks[staged:upto], np.int32)))
        # Append data BEFORE bumping the counters: the server's long-poll
        # reads chunks_staged without the lock, so a reader that sees N
        # staged chunks must find N chunk_data entries.
        rec["chunk_data"].append((k_np, v_np))
        rec["chunk_blocks"].append(upto - staged)
        rec["blocks_staged"] = upto
        rec["chunks_staged"] += 1

    def _finalize_chunk_export(self, rec: dict[str, Any],
                               blocks: list[int]) -> None:
        """Completion staging for a chunk-streamed export: the remaining
        blocks (including the final partial block) become the last chunk,
        sliced out of the full gathered arrays _op_stage_kv just staged,
        and the record flips ``complete`` — the decode peer's long-poll
        terminates. Exports whose pages were never host-addressable
        (sharded) carry no chunk_data; they complete with zero chunks and
        the peer falls back to the full-payload GET."""
        if "chunks_staged" not in rec:
            rec.update({"chunk_data": [], "chunk_blocks": [],
                        "chunks_staged": 0, "blocks_staged": 0})
        staged = int(rec["blocks_staged"])
        n = len(blocks)
        if (n > staged and rec.get("k") is not None
                and getattr(rec["k"], "is_fully_addressable", True)
                and not self._dist):
            rec["chunk_data"].append(pages.block_range(
                np.asarray(rec["k"]), np.asarray(rec["v"]), staged, n))
            rec["chunk_blocks"].append(n - staged)
            rec["blocks_staged"] = n
            rec["chunks_staged"] += 1
        rec["complete"] = True

    def _drop_partial_export(self, request_id: str) -> None:
        """Reclaim a partially-staged chunk export whose prefill died
        mid-stream (abort / window failure): the decode peer's next poll
        404s and it falls back to local prefill. Completed exports are
        never touched — a pulled-but-unreleased record stays for the TTL
        sweep."""
        with self._exports_lock:
            rec = self.kv_exports.get(request_id)
            if rec is not None and not rec.get("complete", True):
                self.kv_exports.pop(request_id, None)

    def _advance_prefills(self):
        """Write windows for the PREFILLING slots ahead of the next decode
        chunk: one for each such slot, at most PREFILL_STEP_TOKENS of prompt
        a step, the request that arrived first served first until its
        prompt is written. A chunk's device time is its weight reads, whatever
        its lane count, so every slot still prefilling is a lane the chunk
        pays for and does not use: one window a step admits long prompts at
        (1 / step) windows a second and leaves three quarters of the lanes
        empty (ROADMAP S12). A lone long prompt among decoding lanes still
        gets one window a step (the cadence prefill_chunk exists to keep).
        By arrival, not by slot index: a request admitted into a low slot
        would overtake every older one in a higher slot at each turnover,
        and those wait without bound."""
        waiting = sorted((s.req.arrival_time, idx) for idx, s in
                         enumerate(self.slots) if s is not None and s.prefilling)
        if not waiting:
            return
        budget = min(len(waiting),
                     max(1, PREFILL_STEP_TOKENS // self._prefill_window()))
        for _, idx in waiting:
            while budget and self.slots[idx].prefilling:
                self._write_prefill_window(idx)
                budget -= 1

    def _write_prefill_window(self, idx: int) -> None:
        """One window of slot idx's prompt into its pages: the one place a
        prompt's programs are dispatched from. A first window with nothing
        cached before it is a plain prefill; any other continues from the
        (block-aligned) pages already written, a prefix-cache hit's or an
        earlier window's alike. The last window's fused sample becomes the
        pending first token, and with it the prompt's whole blocks are
        content-addressed and published. A dispatch that fails ends the
        request (ABORT, its blocks back) and raises."""
        s = self.slots[idx]
        window = self._next_window(s)
        last = len(window) == len(s.prefill_rest)
        written = s.prefill_written
        block = self.mcfg.kv_block_size
        req = s.req
        row = np.zeros((1, self.max_blocks_per_seq), np.int32)
        row[0, : len(s.blocks)] = s.blocks
        # The last window's sample is the slot's first token; the others'
        # are nobody's. Where the slot also names the window's state
        # (kvcache/state.py) every window is the slot's: the token an earlier
        # one leaves there is overwritten before any chunk reads it.
        slots = np.asarray([idx if last or self.geom.state
                            else self.cfg.max_batch], np.int32)
        try:
            bucket = self._bucket(len(window))
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, : len(window)] = window
            n = np.asarray([len(window)], np.int32)
            riders = self._window_tables(
                [(s.blocks, written, written + len(window), True)], 1)
            if req.mm_embeds is not None:
                # (No riders: submit() refuses an image prompt wherever the
                # cache is not plain K/V pages.)
                mm_pad, pos_pad = self._mm_operands(req, bucket)
                tok_dev = self._device_call(
                    ("mm_prefill", bucket, mm_pad.shape[1]), dict(
                        tokens=tokens, seq_len=n, mm_pad=mm_pad,
                        pos_pad=pos_pad, row=row, slots=slots,
                        **self._sample_np([req])))
            elif written == 0:
                tok_dev = self._device_call(("prefill", bucket), dict(
                    tokens=tokens, seq_len=n, row=row, slots=slots, **riders,
                    **self._sample_np([req])))
            else:
                # The blocks before this window, to a power of two of them
                # (padding → trash): a continuation costs O(prefix).
                prior_n = written // block
                pb = 1
                while pb < prior_n:
                    pb *= 2
                pb = min(pb, self.max_blocks_per_seq)
                prior = np.zeros((1, pb), np.int32)
                prior[0, :prior_n] = s.blocks[:prior_n]
                tok_dev = self._device_call(
                    ("prefix_prefill", bucket, pb), dict(
                        tokens=tokens, suffix_len=n,
                        prefix_len=np.asarray([written], np.int32),
                        row=row, prior=prior, slots=slots, **riders,
                        **self._sample_np([req])))
        except Exception:
            # (A predecessor that _place retired stays retired: the chunk in
            # flight books it.)
            self.slots[idx] = None
            self._drop_partial_export(req.request_id)
            with self._cond:
                self.allocator.free(s.blocks)
                self.telemetry.observe_allocator(self.allocator)
            self._emit_to(s.out, s.loop, TokenEvent(
                request_id=req.request_id, token_id=None,
                finish_reason=FinishReason.ABORT,
                prompt_tokens=s.prompt_len))
            self.telemetry.running.set(
                sum(x is not None for x in self.slots))
            raise
        self.telemetry.prompt_tokens.inc(len(window))
        s.prefill_written = written + len(window)
        s.prefill_rest = s.prefill_rest[len(window):]
        if not last:
            # Chunk-streamed remote-decode prefill: stage the window's
            # newly COMPLETE blocks so a decode peer's long-poll pulls
            # chunk k while chunk k+1 computes. The final (partial)
            # block rides the completion staging in _finish_slot.
            self._maybe_stage_chunk(s)
            return
        # The slot lands PENDING: the first token is still on the device
        # (transfer in flight; the samples of the windows before it were
        # nobody's). _finalize_prefills completes it after the decode chunk
        # for the established lanes has been dispatched, hiding the readback
        # behind device work.
        hashes, caching = s.chunk_meta
        s.chunk_meta = None
        s.prefilling = False
        s.pending_tok = tok_dev
        n_complete = s.prompt_len // block
        matched_n = s.cached_tokens // block
        if caching:
            # Content-address the freshly computed complete prompt blocks.
            with self._cond:
                self.allocator.commit_hashes(
                    s.blocks[matched_n:n_complete],
                    hashes[matched_n:n_complete])
        s.block_hashes = hashes[:n_complete]
        if self.kv_events is not None and s.block_hashes:
            self.kv_events.stored(s.block_hashes)

    def _mm_operands(self, req, bucket: int):
        """An image prompt's two operands more: its encoder vectors padded
        to a power of two of them, and where in the prompt each goes."""
        mm = np.asarray(req.mm_embeds, np.float32)
        mm_bucket = 1
        while mm_bucket < mm.shape[0]:
            mm_bucket *= 2
        mm_pad = np.zeros((1, mm_bucket, mm.shape[1]), np.float32)
        mm_pad[0, : mm.shape[0]] = mm
        # Padding positions land out of range → dropped by the scatter.
        # Missing/short mm_positions default to an image-first layout.
        positions = list(req.mm_positions or [])
        while len(positions) < mm.shape[0]:
            positions.append(len(positions))
        pos_pad = np.full((1, mm_bucket), bucket, np.int32)
        pos_pad[0, : mm.shape[0]] = positions[: mm.shape[0]]
        return mm_pad, pos_pad

    # ---- P/D import (decode side) --------------------------------------

    def _start_kv_fetch(self, req, out, loop):
        """Fetch the prefiller's staged KV on a separate thread (the engine
        thread must keep decoding while the transfer happens). Device-first:
        pull directly device-to-device via the transfer server when both
        sides have one; fall back to the host-staged HTTP path."""
        pi = _PendingImport(req=req, out=out, loop=loop)
        ktp = req.kv_transfer_params or {}
        with self._cond:
            self._kv_fetching += 1

        def fetch():
            try:
                self._fetch_inner(pi, ktp)
            finally:
                with self._cond:
                    self._kv_fetching -= 1
                    self._cond.notify()

        threading.Thread(target=fetch, name="kv-fetch", daemon=True).start()

    KV_IMPORT_STATS_CAP = 512

    def _note_kv_import(self, request_id: str, t0: float,
                        nbytes: int | None, route: str,
                        exposed_ms: float | None = None) -> None:
        """Record one completed pull's duration/bytes for the server to
        stamp on the decode response (x-kv-pull-ms/-bytes → the router's
        per-pair /debug/transfers table). Chunk-streamed pulls also carry
        ``exposed_ms`` — the non-overlapped tail (x-kv-pull-exposed-ms)."""
        # A re-dispatched request id overwrites its dict entry; appending a
        # duplicate ring slot too would make a later eviction pop the LIVE
        # entry when the stale first occurrence reaches the front.
        if request_id not in self.kv_import_stats:
            self._kv_import_order.append(request_id)
        stats = {
            "ms": (time.monotonic() - t0) * 1e3,
            "bytes": int(nbytes or 0),
            "route": route,
        }
        if exposed_ms is not None:
            stats["exposed_ms"] = exposed_ms
        self.kv_import_stats[request_id] = stats
        while len(self._kv_import_order) > self.KV_IMPORT_STATS_CAP:
            self.kv_import_stats.pop(self._kv_import_order.popleft(), None)

    def _note_prefix_hit(self, request_id: str, hit_tokens: int,
                         prompt_tokens: int, *, kind: str = "prefill") -> None:
        """Record the ACTUAL prefix-cache hit depth for one request at
        prefill admission (matched blocks x block size over the full
        prompt) — see telemetry.PrefixHitLog for the record/eviction
        discipline shared with the sim."""
        self.kv_hits.note(request_id, hit_tokens, prompt_tokens, kind=kind)

    def _fetch_inner(self, pi, ktp):
        """The fetch-thread body: resolve a transfer route, move the bytes
        (or record the error), and hand the pending import to the engine
        thread via _import_ready."""
        t0 = time.monotonic()
        if (ktp.get("transfer_shards") and ktp.get("kv_mesh")
                and (self.kv_transfer_server is not None
                     or self.kv_shard_wire is not None)):
            # Sharded exporter. Multi-host importer: only preflight here
            # (the pull is a coordinated engine-thread op); single-proc
            # importer pulls every shard from the one exporter address.
            try:
                self._check_shard_geometry(ktp)
                if self._dist:
                    wire_addrs = (ktp.get("shard_wire_addrs")
                                  if self._kv_wire == "host"
                                  else ktp["transfer_shards"])
                    if not wire_addrs or not all(wire_addrs):
                        raise ValueError(
                            f"no usable {self._kv_wire} wire addresses")
                    for addr in wire_addrs:
                        _tcp_preflight(addr)
                    pi.dist_pull = True
                    with self._cond:
                        self._import_ready.append(pi)
                        self._cond.notify()
                    return
                self._pull_device_kv_sharded(pi, ktp)
                self.kv_import_device_count += 1
                self._note_kv_import(pi.req.request_id, t0,
                                     wire.param_bytes(ktp), "device")
                with self._cond:
                    self._import_ready.append(pi)
                    self._cond.notify()
                return
            except Exception as e:
                self.kv_import_device_error = f"{type(e).__name__}: {e}"
                log.warning("sharded kv pull (%s) failed (%s); "
                            "host-path fallback",
                            ktp.get("transfer_shards"), e)
        if (ktp.get("transfer_address") and ktp.get("kv_shape")
                and not self._dist
                and self.kv_transfer_server is not None):
            try:
                self._pull_device_kv(pi, ktp)
                self.kv_import_device_count += 1
                self._note_kv_import(pi.req.request_id, t0,
                                     wire.param_bytes(ktp), "device")
                with self._cond:
                    self._import_ready.append(pi)
                    self._cond.notify()
                return
            except Exception as e:
                self.kv_import_device_error = f"{type(e).__name__}: {e}"
                log.warning("device kv pull from %s failed (%s); "
                            "falling back to host path",
                            ktp["transfer_address"], e)
        if self._dist:
            # No host path on a multi-host mesh (pages are not fully
            # addressable): degrade to local prefill directly.
            pi.error = "no usable sharded transfer route"
            with self._cond:
                self._import_ready.append(pi)
                self._cond.notify()
            return
        import httpx

        scheme = ktp.get("remote_scheme") or "http"
        url = (f"{scheme}://{ktp['remote_host']}:{ktp['remote_port']}"
               f"/kv/{ktp['remote_request_id']}")
        verify = self._client_tls_verify()
        try:
            if ktp.get("stream_chunks"):
                self._pull_host_chunks(pi, ktp, url, verify, t0)
            else:
                r = httpx.get(url, timeout=30.0, verify=verify)
                r.raise_for_status()
                pi.payload = r.content
                pi.headers = dict(r.headers)
                self.kv_import_host_count += 1
                self._note_kv_import(pi.req.request_id, t0,
                                     len(r.content), "host")
            try:
                httpx.delete(url, timeout=5.0, verify=verify)
            except Exception:
                pass  # exporter TTL sweep reclaims
        except Exception as e:
            pi.error = str(e)
        with self._cond:
            self._import_ready.append(pi)
            self._cond.notify()

    # Overall stall bound for one chunk-streamed pull (the per-poll
    # long-poll bound is the server's KV_CHUNK_WAIT_CAP_MS).
    KV_CHUNK_STREAM_TIMEOUT_S = 120.0

    def _pull_host_chunks(self, pi, ktp, url: str, verify, t0: float) -> None:
        """Pipelined host pull: long-poll ``?chunk=N`` so chunk k moves
        while the prefill peer computes chunk k+1, then assemble the full
        payload + synthesized geometry headers for the regular import path.
        An exporter that never staged chunks (sharded pages) completes with
        zero chunks — degrade to the legacy full-payload GET. Raises on any
        protocol failure; the caller records pi.error and the engine falls
        back to local prefill (zero client-visible errors)."""
        import httpx

        chunks: list[tuple[dict[str, str], bytes]] = []
        complete_at: float | None = None
        meta: dict[str, str] = {}
        deadline = t0 + self.KV_CHUNK_STREAM_TIMEOUT_S
        while True:
            if time.monotonic() > deadline:
                raise TimeoutError("kv chunk stream stalled")
            r = httpx.get(url, params={"chunk": len(chunks), "wait_ms": 2000},
                          timeout=30.0, verify=verify)
            if r.status_code == 202:  # chunk not staged yet: re-poll
                continue
            if r.status_code == 204:  # complete, no further chunks
                meta = dict(r.headers)
                if complete_at is None:
                    complete_at = time.monotonic()
                break
            r.raise_for_status()
            hdrs = dict(r.headers)
            if hdrs.get("x-kv-complete") == "1" and complete_at is None:
                complete_at = time.monotonic()
            chunks.append((hdrs, r.content))
            if (hdrs.get("x-kv-complete") == "1"
                    and len(chunks) >= int(hdrs.get("x-kv-chunks-staged")
                                           or 0)):
                meta = hdrs
                break
        joined = wire.join_chunks(chunks)
        if joined is None:
            # Exporter had no host-addressable chunks: full-payload GET.
            r = httpx.get(url, timeout=30.0, verify=verify)
            r.raise_for_status()
            pi.payload = r.content
            pi.headers = dict(r.headers)
            self.kv_import_host_count += 1
            self._note_kv_import(pi.req.request_id, t0,
                                 len(r.content), "host")
            return
        pi.payload, pi.headers = joined
        pi.headers["x-kv-seq-len"] = meta["x-kv-seq-len"]
        pi.headers["x-kv-first-token"] = meta.get("x-kv-first-token", "")
        self.kv_import_host_count += 1
        t_end = time.monotonic()
        exposed_ms = (t_end - max(complete_at or t0, t0)) * 1e3
        self._note_kv_import(pi.req.request_id, t0, len(pi.payload),
                             "host-chunked", exposed_ms=exposed_ms)

    def _check_shard_geometry(self, ktp: dict[str, Any]) -> None:
        """A sharded pull needs identical page-sharding geometry on both
        sides (symmetric P/D deployment); mismatch falls back."""
        from .kv_shards import mesh_descriptor

        mesh = self._page_mesh()
        if mesh is None:
            raise ValueError("importer is unsharded; exporter pages are "
                             "sharded — host path required")
        mine = mesh_descriptor(mesh, pages.page_spec(mesh))
        theirs = ktp["kv_mesh"]
        if mine != theirs:
            raise ValueError(f"page sharding mismatch: {theirs} vs {mine}")
        if len(ktp["transfer_shards"]) != int(theirs["n_procs"]):
            raise ValueError("shard descriptor count != exporter processes")

    def _pull_device_kv_sharded(self, pi: _PendingImport,
                                ktp: dict[str, Any]) -> None:
        """Single-process importer, sharded exporter/importer pages: pull
        every unique shard from the exporter and assemble under the local
        page sharding."""
        addr = ktp["transfer_shards"][0]
        _tcp_preflight(addr)
        pi.k_dev, pi.v_dev = self._pull_sharded_arrays(
            addr, int(ktp["transfer_uuid"]),
            tuple(int(d) for d in ktp["kv_shape"]),
            jnp.dtype(ktp["kv_dtype"]))
        self._release_remote_export(ktp)

    def _client_tls_verify(self):
        """TLS verification policy for the engine's outbound HTTP legs
        (host-staged /kv pulls + release DELETEs): default skip-verify for
        pod-local certs, or the configured CA bundle. Memoized —
        the config is immutable after startup and SSLContext construction is
        not free on the latency-sensitive transfer path."""
        verify = getattr(self, "_http_verify", None)
        if verify is None:
            from ..router.tlsutil import client_verify

            verify = client_verify(self.cfg.client_insecure_skip_verify,
                                   self.cfg.client_ca_cert_path or None)
            self._http_verify = verify
        return verify

    def _release_remote_export(self, ktp: dict[str, Any]) -> None:
        """Best-effort: tell the exporter its staged copy was consumed
        device-side so it drops the record without self-draining."""
        try:
            import httpx

            scheme = ktp.get("remote_scheme") or "http"
            httpx.delete(f"{scheme}://{ktp['remote_host']}:"
                         f"{ktp['remote_port']}"
                         f"/kv/{ktp['remote_request_id']}?consumed=device",
                         timeout=5.0, verify=self._client_tls_verify())
        except Exception:
            pass  # exporter TTL sweep reclaims

    def _pull_device_kv(self, pi: _PendingImport, ktp: dict[str, Any]) -> None:
        """Device-to-device pull: KV lands on this engine's device directly
        (ICI same-slice, DCN cross-slice — runtime-routed)."""
        from jax.sharding import SingleDeviceSharding

        # TCP preflight: the transfer layer blocks indefinitely on an
        # unreachable peer; fail fast here so the HTTP fallback engages.
        _tcp_preflight(ktp["transfer_address"])

        shape = tuple(int(d) for d in ktp["kv_shape"])
        dtype = jnp.dtype(ktp["kv_dtype"])
        sds = jax.ShapeDtypeStruct(shape, dtype,
                                   sharding=SingleDeviceSharding(self.device))
        conn = self._transfer_conn(ktp["transfer_address"])
        pi.k_dev, pi.v_dev = conn.pull(int(ktp["transfer_uuid"]), [sds, sds])
        pi.k_dev.block_until_ready()
        # Release the prefiller's export record, flagging device consumption
        # so it does NOT self-drain the (already pulled) staging uuid.
        self._release_remote_export(ktp)

    def _process_imports(self):
        while True:
            free = [i for i, s in enumerate(self.slots) if s is None]
            with self._cond:
                if not self._import_ready or not free:
                    return
                pi = self._import_ready[0]
                blocks: list[int] = []
                evicted: list[int] = []
                if pi.error is None:
                    need = self._blocks_needed(pi.req)
                    available = getattr(self.allocator, "reusable_blocks",
                                        self.allocator.free_blocks)
                    if need > available:
                        return  # wait for capacity
                    blocks = self.allocator.alloc(need)
                    evicted = list(getattr(self.allocator,
                                           "last_evicted_hashes", []))
                    self.telemetry.observe_allocator(self.allocator)
                self._import_ready.pop(0)
            if evicted and self.kv_events is not None:
                self.kv_events.removed(evicted)
            if pi.error is None:
                try:
                    self._import_into_slot(free[0], pi, blocks)
                    continue
                except Exception as e:
                    # Malformed payload/headers or geometry mismatch: reclaim
                    # the allocation and degrade to local prefill.
                    with self._cond:
                        self.allocator.free(blocks)
                        self.telemetry.observe_allocator(self.allocator)
                    pi.error = f"import rejected: {e}"
            # Reference semantics: fall back to local prefill on transfer
            # failure (connector_nixlv2.go:160-177).
            log.warning("kv import for %s failed (%s); local prefill fallback",
                        pi.req.request_id, pi.error)
            with self._cond:
                self._waiting.insert(0, (self._strip_remote(pi.req), pi.out, pi.loop))
                self.telemetry.waiting.set(len(self._waiting))

    @staticmethod
    def _strip_remote(req: EngineRequest) -> EngineRequest:
        return dataclasses.replace(req, kv_transfer_params=None)

    def _import_into_slot(self, idx: int, pi: _PendingImport, blocks: list[int]):
        """Validates and scatters fetched KV — device arrays from the
        transfer-server pull, or host bytes from the HTTP path; raises on any
        malformed/mismatched import (caller falls back to local prefill)."""
        req, headers = pi.req, pi.headers or {}
        ktp = req.kv_transfer_params or {}
        # The exporter's un-padded block count; absent means not padded.
        remote_nb = int(ktp.get("remote_num_blocks") or 0) or None
        if pi.dist_pull:
            # Coordinated multi-host pull: every process fetches its shards
            # from its counterpart prefill process and scatters, in lockstep.
            shape = tuple(int(d) for d in ktp["kv_shape"])
            seq_len = int(ktp["remote_seq_len"])
            nb, real_nb = wire.validate(self.geom, shape, seq_len, remote_nb,
                                        len(blocks))
            padded_blocks = np.zeros((nb,), np.int32)
            padded_blocks[:real_nb] = blocks[:real_nb]
            self._device_call(("pull_kv_import",), dict(
                blocks_pad=padded_blocks,
                addresses=list(ktp["transfer_shards"]),
                shard_addrs=list(ktp.get("shard_wire_addrs") or []),
                tuid=int(ktp["transfer_uuid"]),
                shape=[int(d) for d in shape],
                dtype=str(ktp["kv_dtype"])))
            if self._kv_wire == "host":
                self.kv_import_host_count += 1
            else:
                self.kv_import_device_count += 1
            self._release_remote_export(ktp)
        elif pi.k_dev is not None:
            # Device path: already on this engine's device; scatter directly.
            # The staging side pow2-pads the block dim, so the per-shape jit
            # cache stays at log2(max_blocks)+1 entries; padding rows scatter
            # into the trash block 0.
            seq_len = int(ktp["remote_seq_len"])
            nb, real_nb = wire.validate(
                self.geom, tuple(int(d) for d in pi.k_dev.shape), seq_len,
                remote_nb, len(blocks))
            padded_blocks = np.zeros((nb,), np.int32)  # tail → trash block 0
            padded_blocks[:real_nb] = blocks[:real_nb]
            self.k_pages, self.v_pages = self._jit_import(
                self.k_pages, self.v_pages, jnp.asarray(padded_blocks),
                pi.k_dev, pi.v_dev)
        else:
            k_np, v_np, seq_len, real_nb = wire.decode(
                self.geom, headers, pi.payload, len(blocks))
            # Pad to the fixed per-seq block budget so the scatter compiles once.
            maxB = self.max_blocks_per_seq
            k_pad, v_pad = pages.pad_blocks(k_np, v_np, maxB)
            blocks_pad = np.zeros((maxB,), np.int32)  # padding lands in trash block 0
            blocks_pad[:real_nb] = blocks[:real_nb]
            self._device_call(("import",), dict(
                blocks_pad=blocks_pad, k_pad=k_pad, v_pad=v_pad))

        first = int(ktp.get("remote_first_token")
                    if ktp.get("remote_first_token") is not None
                    else headers["x-kv-first-token"])
        # The host holds this slot's first token: the chunk wants it on the
        # device.
        self._device_call(("keep_tokens",), dict(
            slots=np.asarray([idx], np.int32),
            toks=np.asarray([first], np.int32)))
        slot = _Slot(req=req, out=pi.out, loop=pi.loop, blocks=blocks,
                     position=seq_len, generated=[first],
                     cached_tokens=seq_len)
        hashes = chain_block_hashes(self.model_name,
                                    req.prompt_token_ids[:seq_len], "",
                                    self.mcfg.kv_block_size)
        n_complete = seq_len // self.mcfg.kv_block_size
        slot.block_hashes = hashes[:n_complete]
        if isinstance(self.allocator, PrefixCachingAllocator):
            with self._cond:
                self.allocator.commit_hashes(blocks[:n_complete],
                                             hashes[:n_complete])
        if self.kv_events is not None and slot.block_hashes:
            self.kv_events.stored(slot.block_hashes)
        self._place(idx, slot)
        self._observe_first_token(req)
        self._emit(slot, TokenEvent(
            request_id=req.request_id, token_id=first,
            text=self.tokenizer.decode([first]), is_first=True,
            prompt_tokens=seq_len, completion_tokens=1,
            cached_tokens=seq_len))
        slot.first_emitted = True
        self._maybe_finish_after_token(idx, slot, first)

    # ---- decode --------------------------------------------------------

    def _sample_np(self, reqs) -> dict[str, np.ndarray]:
        """Host-side sampling knobs for a batch of requests (shipped to
        followers verbatim; the PRNG key is NOT shipped — every process
        derives it from the same seeded stream inside the op)."""
        return {
            "temps": np.array([r.temperature for r in reqs], np.float32),
            "top_k": np.array([r.top_k for r in reqs], np.int32),
            "top_p": np.array([r.top_p for r in reqs], np.float32),
        }

    def _next_key(self, warm: bool):
        """Next sampling subkey. warm=True uses a fixed throwaway key so
        warm-up compiles consume nothing from the seeded stream (keeps
        outputs warmup-flag-independent AND leader/follower streams in
        lockstep without a restore op)."""
        if warm:
            return self._put_key(jax.random.key(0xC0FFEE))
        self._sample_key, sub = jax.random.split(self._sample_key)
        return self._put_key(sub)

    def _put(self, x):
        """Host input → device. Multi-host: fully-replicated global array on
        the mesh (every process feeds identical bytes — device_put can't
        target non-addressable devices, so this goes through
        make_array_from_process_local_data); otherwise a plain local
        transfer."""
        if self._dist:
            from jax.sharding import NamedSharding, PartitionSpec

            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh or self.pp_mesh, PartitionSpec()),
                np.asarray(x))
        return self._on_device(x)

    def _on_device(self, x):
        """Commit a single-device engine's input to its bound device, so the
        jitted step runs there and not on the process's default device. A
        mesh engine's inputs stay uncommitted: the step's sharded params
        decide where they go."""
        if self.mesh is None and self.pp_mesh is None:
            return jax.device_put(x, self.device)
        return jnp.asarray(x)

    def _put_key(self, key):
        """Typed PRNG keys can't round-trip through numpy: globalize the raw
        key data and re-wrap."""
        if self._dist:
            kd = self._put(np.asarray(jax.random.key_data(key)))
            return jax.random.wrap_key_data(kd)
        return key

    # ---- device ops (multihost-replayable) -----------------------------
    # Every device call the engine loop makes goes through _device_call so
    # follower processes (engine/multihost.py) can replay the identical jit
    # sequence. Op args are plain numpy/int — never device arrays.

    @staticmethod
    def _op_shape_key(op: tuple, args: dict) -> tuple[str, str] | None:
        """Stable (op, shape-bucket) identity of a dispatch — the same key
        space the jit caches trace on, so 'first time seen' == 'compiles'.
        Ops with no per-shape jit variant (release/stage plumbing) are None."""
        kind = op[0]
        if kind == "decode":
            return ("decode", f"{len(args['slots'])}x{args['tables'].shape[1]}")
        if kind == "prefill":
            return ("prefill", f"{args['tokens'].shape[0]}x{op[1]}")
        if kind == "prefix_prefill":
            return ("prefix_prefill", f"{op[1]}x{op[2]}")
        if kind == "mm_prefill":
            return ("mm_prefill", f"{op[1]}x{op[2]}")
        if kind == "embed":
            return ("embed", str(op[1]))
        return None

    def _device_call(self, op: tuple, args: dict):
        if self._instr_channel is not None and self._instr_channel.leader:
            self._instr_channel.broadcast(op, args)
        key = self._op_shape_key(op, args)
        decode = op[0] == "decode"
        self._calls_since_chunk += not decode
        if key is None:
            return self._exec_op(op, args)
        # Rows (padded tokens) of one step of this program, and its steps:
        # the model's family says what they count as.
        rows = args["slots" if decode else "tokens"].size
        steps = args["steps"] if decode else 1
        real, queries = self._requests_part(op, args)
        self.telemetry.book_program(self.bound.program_counts(
            op[0], rows, steps, real=real, queries=queries))
        t0 = time.monotonic()
        result = self._exec_op(op, args)
        dt = time.monotonic() - t0
        if key not in self._seen_op_shapes:
            self._seen_op_shapes.add(key)
            self._period_first_call = True
            self.telemetry.compile_events.labels(op=key[0], bucket=key[1]).inc()
            self.telemetry.compile_duration.observe(dt)
        elif key[0] in ("prefill", "prefix_prefill", "mm_prefill"):
            # Dispatch wall time (the decode chunk's own wall time is
            # measured in _land_chunk instead, where the sync is).
            self.telemetry.prefill_step.observe(dt)
        return result

    def _requests_part(self, op: tuple, args: dict):
        """What of a program is somebody's, from what the host holds of it:
        (its sequences that are a request's, their runs of query tokens as
        (the context of each run's first query, its length)). A decode
        chunk's steps are its real lanes' next positions, one a step; a
        warm-up program is nobody's."""
        if args.get("warm") or "slots" not in args:
            return 0, None
        real = args["slots"] < self.cfg.max_batch
        if op[0] == "decode":
            first = args["positions"][real] + 1
            queries = first, np.full(first.shape, args["steps"])
        elif op[0] == "prefill":
            queries = np.ones_like(args["seq_len"]), args["seq_len"]
        elif op[0] == "prefix_prefill":
            queries = args["prefix_len"] + 1, args["suffix_len"]
        else:
            queries = None
        return int(real.sum()), queries

    def _exec_op(self, op: tuple, args: dict):
        kind = op[0]
        if kind == "decode":
            return self._op_decode(**args)
        if kind == "prefill":
            return self._op_prefill(op[1], **args)
        if kind == "prefix_prefill":
            return self._op_prefix_prefill(op[1], op[2], **args)
        if kind == "mm_prefill":
            return self._op_mm_prefill(op[1], op[2], **args)
        if kind == "import":
            return self._op_import(**args)
        if kind == "keep_tokens":
            return self._op_keep_tokens(**args)
        if kind == "stage_kv":
            return self._op_stage_kv(**args)
        if kind == "release_kv_export":
            return self._op_release_export(**args)
        if kind == "pull_kv_import":
            return self._op_pull_kv_import(**args)
        if kind == "embed":
            return self._op_embed(op[1], **args)
        raise ValueError(f"unknown device op {op!r}")

    def _shard_addresses(self) -> list[str]:
        """Per-process transfer addresses in process order (self first when
        leading): a sharded importer pulls its shards from its counterpart
        process. Single-process: just this engine's address. "" marks a
        process with no transfer server (host-wire deployments) — the
        importer's all()-guard rejects the device wire then."""
        addrs = [self._transfer_address()
                 if self.kv_transfer_server is not None else ""]
        if self._instr_channel is not None and self._instr_channel.leader:
            for pid in range(1, self.cfg.dist_num_processes):
                hello = self._instr_channel.hellos.get(pid) or {}
                addrs.append(hello.get("transfer_address") or "")
        return addrs

    def _shard_wire_addresses(self) -> list[str]:
        """Per-process host shard-wire addresses, process order (dist only)."""
        if self.kv_shard_wire is None:
            return []
        addrs = [self.kv_shard_wire.address()]
        if self._instr_channel is not None and self._instr_channel.leader:
            for pid in range(1, self.cfg.dist_num_processes):
                hello = self._instr_channel.hellos.get(pid) or {}
                addrs.append(hello.get("shard_wire_address") or "")
        return addrs

    def _op_stage_kv(self, request_id: str, idx: np.ndarray, tuid: int,
                     stream: bool = False):
        """Gather the export's blocks out of the (possibly sharded) pages
        and register this process's unique shards under ``tuid``. Runs on
        every process under dist (the gather is a collective program on
        global arrays). Unsharded engines degenerate to the legacy [k, v]
        registration."""
        from .kv_shards import local_unique_shards

        mesh = self._page_mesh()
        idx_dev = self._put(idx)
        if mesh is not None:
            if self._jit_stage is None:
                out_sh = pages.page_sharding(mesh)
                self._jit_stage = jax.jit(pages.gather_blocks,
                                          out_shardings=(out_sh, out_sh))
            k_stage, v_stage = self._jit_stage(self.k_pages, self.v_pages,
                                               idx_dev)
        else:
            k_stage, v_stage = pages.gather_blocks(self.k_pages,
                                                   self.v_pages, idx_dev)
        staged_shards = None
        registered = None
        wire_uuid = None
        shards = None
        if self.kv_transfer_server is not None or self.kv_shard_wire is not None:
            shards = (local_unique_shards(k_stage)
                      + local_unique_shards(v_stage))
        if self.kv_shard_wire is not None:
            # Host shard wire: every process serves its own shard list; the
            # registry holds the device arrays, D2H happens at pull time.
            self.kv_shard_wire.register(tuid, shards)
            wire_uuid = tuid
        if (self.kv_transfer_server is not None
                and not (self._dist and self._kv_wire == "host")):
            # Skip the transfer-server registration when the resolved wire is
            # host-staged (cpu backend): nothing would ever pull it, and the
            # release path would have to self-drain every export.
            try:
                self.kv_transfer_server.await_pull(tuid, shards)
                staged_shards = shards
                registered = tuid
            except Exception:
                if (self._instr_channel is not None
                        and not self._instr_channel.leader):
                    # A follower whose registration is missing would HANG the
                    # importer's pull — crash loudly (run_follower exits,
                    # the group restarts) instead of wedging the peer slice.
                    raise
                log.exception("kv await_pull failed; host path only")
        # transfer_uuid is the wire-advertised pull id whichever wire carried
        # the registration; staged_shards stays None unless the transfer
        # server holds a registration (it gates the self-drain on release).
        rec = {"k": k_stage, "v": v_stage,
               "transfer_uuid": registered if registered is not None else wire_uuid,
               "shard_wire_uuid": wire_uuid,
               "staged_shards": staged_shards, "created": time.monotonic()}
        with self._exports_lock:
            prev = self.kv_exports.get(request_id)
            if prev is not None and "chunks_staged" in prev:
                # Chunk-streamed prefill staged partial chunks already:
                # carry them into the completed record (the decode peer may
                # be mid-pull against them right now).
                for key in ("chunk_data", "chunk_blocks", "chunks_staged",
                            "blocks_staged"):
                    rec[key] = prev[key]
                rec["complete"] = False  # _finalize_chunk_export flips it
            elif stream:
                # Short-prompt stream_chunks export (no mid-prefill chunks):
                # a pre-assigned-rid puller may already be polling, so the
                # record must read INCOMPLETE until the finish path stamps
                # its metadata and stages the single chunk.
                rec.update({"chunk_data": [], "chunk_blocks": [],
                            "chunks_staged": 0, "blocks_staged": 0,
                            "complete": False})
            self.kv_exports[request_id] = rec
        return rec

    def _op_release_export(self, request_id: str, consumed: str):
        self._release_export_local(request_id, consumed)

    def _op_pull_kv_import(self, blocks_pad: np.ndarray, addresses: list[str],
                           tuid: int, shape: tuple, dtype: str,
                           shard_addrs: list[str] | None = None):
        """Coordinated sharded pull + scatter (dist decode side): every
        process pulls its unique page shards from its counterpart prefill
        process — over the device transfer server or the host shard wire,
        per the resolved kv_wire — assembles the global staged array, and
        runs the same scatter op as a local import. A process whose pull
        fails raises — under dist that is a group-restart fault (the other
        processes are already inside the op)."""
        if self._kv_wire == "host" and shard_addrs:
            k_dev, v_dev = self._pull_sharded_arrays_host(
                shard_addrs[jax.process_index()], tuid, tuple(shape),
                jnp.dtype(dtype))
        else:
            k_dev, v_dev = self._pull_sharded_arrays(
                addresses[jax.process_index()], tuid, tuple(shape),
                jnp.dtype(dtype))
        self.k_pages, self.v_pages = self._jit_import(
            self.k_pages, self.v_pages, self._put(blocks_pad), k_dev, v_dev)

    def _pull_sharded_arrays(self, address: str, tuid: int,
                             shape: tuple, dtype) -> tuple[Any, Any]:
        """Pull this process's unique shards of a staged [k, v] pair from
        ``address`` and assemble the global arrays under the local page
        sharding (replica devices get device_put copies)."""
        from jax.sharding import SingleDeviceSharding

        from .kv_shards import local_shard_groups

        sharding = pages.page_sharding(self._page_mesh())
        groups = local_shard_groups(sharding, shape)
        shard_shape = sharding.shard_shape(shape)
        sds = [jax.ShapeDtypeStruct(shard_shape, dtype,
                                    sharding=SingleDeviceSharding(devs[0]))
               for _, devs in groups]
        conn = self._transfer_conn(address)
        pulled = conn.pull(int(tuid), sds + sds)
        k_shards, v_shards = pulled[:len(groups)], pulled[len(groups):]

        def assemble(shards):
            arrays = []
            for (_, devs), arr in zip(groups, shards):
                arrays.append(arr)
                arrays.extend(jax.device_put(arr, d) for d in devs[1:])
            return jax.make_array_from_single_device_arrays(
                shape, sharding, arrays)

        k_dev, v_dev = assemble(k_shards), assemble(v_shards)
        k_dev.block_until_ready()
        return k_dev, v_dev

    def _pull_sharded_arrays_host(self, address: str, tuid: int,
                                  shape: tuple, dtype) -> tuple[Any, Any]:
        """Host shard wire variant of :meth:`_pull_sharded_arrays`: fetch
        this process's shard bytes from its counterpart's ShardWireServer
        and assemble the global arrays under the local page sharding. Shard
        order on the wire is the exporter's canonical
        local_unique_shards(k) + local_unique_shards(v) — the same order the
        importer's local_shard_groups produces under symmetric geometry
        (enforced by _check_shard_geometry)."""
        from .kv_shards import local_shard_groups
        from .shard_wire import pull_shards

        sharding = pages.page_sharding(self._page_mesh())
        groups = local_shard_groups(sharding, shape)
        shard_shape = sharding.shard_shape(shape)
        arrs = pull_shards(address, int(tuid))
        if len(arrs) != 2 * len(groups):
            raise ValueError(f"shard wire returned {len(arrs)} shards, "
                             f"expected {2 * len(groups)}")
        for a in arrs:
            if tuple(a.shape) != tuple(shard_shape):
                raise ValueError(f"shard shape {a.shape} != {shard_shape}")

        def assemble(shards_np):
            arrays = []
            for (_, devs), np_arr in zip(groups, shards_np):
                np_arr = np_arr.astype(dtype, copy=False)
                arrays.extend(jax.device_put(np_arr, d) for d in devs)
            return jax.make_array_from_single_device_arrays(
                shape, sharding, arrays)

        k_dev = assemble(arrs[:len(groups)])
        v_dev = assemble(arrs[len(groups):])
        k_dev.block_until_ready()
        return k_dev, v_dev

    def _op_decode(self, slots, positions, tables, steps, temps, top_k, top_p,
                   warm=False, wt=None):
        # (The cache takes the host's copy of the slots: what goes in with
        # it is donated with it.) Where the bucket's program reads the chosen
        # experts alone, the cache carries its count of them out.
        visits = self.bound.decode_expert_visits(len(slots)) * steps
        cache = state_pool.at_slots(self.k_pages, slots, wt,
                                    reads=visits > 0)
        slots = self._put(slots)
        args = (self.params, self._jit_slot_tokens(self._slot_tokens, slots),
                self._put(positions), cache, self.v_pages,
                self._put(tables),
                self._next_key(warm), self._put(temps), self._put(top_k),
                self._put(top_p))
        if self.pp_mesh is None:    # (a pp chunk is decode_chunk steps long)
            args += (self._put(np.int32(steps)),)
        if (self.cfg.pallas_attention and not self.cfg.pallas_interpret
                and self.pp_mesh is None):
            # The resolved flag says what was asked for; the lowered text
            # says what the program holds. One extra lowering per shape,
            # beside that shape's compile.
            shape = f"{len(positions)}x{tables.shape[1]}"
            if shape not in self.decode_kernel_in_program:
                self.decode_kernel_in_program[shape] = (
                    "tpu_custom_call"
                    in self._jit_decode_chunk.lower(*args).as_text())
        toks, k_pages, self.v_pages = self._jit_decode_chunk(*args)
        self._keep_cache(k_pages, slots.size * steps, visits)
        return self._op_keep_tokens(slots, toks, row=steps - 1)

    def _op_keep_tokens(self, slots, toks, row=0):
        """Leave sampled tokens where the next chunk finds them: entry i of
        ``toks`` ([N], or row ``row`` of a chunk's [K, N]: its last step's)
        is slot ``slots[i]``'s newest token (max_batch: nobody's). Its own op
        for a token the host holds (an import's first); the tail of every op
        that samples. Starts the tokens' copy to the host."""
        slots, toks = (x if isinstance(x, jax.Array) else self._put(x)
                       for x in (slots, toks))
        self._slot_tokens = self._jit_keep_tokens(
            self._slot_tokens, slots, toks, self._put(np.int32(row)))
        toks.copy_to_host_async()
        return toks

    def _op_prefill(self, bucket, tokens, seq_len, row, slots, temps, top_k,
                    top_p, warm=False, wt=None):
        fn = self._prefill_fn(bucket)
        tok, k_pages, self.v_pages = fn(
            self.params, self._put(tokens), self._put(seq_len),
            state_pool.at_slots(self.k_pages, slots, wt), self.v_pages,
            self._put(row),
            self._next_key(warm), self._put(temps), self._put(top_k),
            self._put(top_p))
        self._keep_cache(k_pages, tokens.size)
        return self._op_keep_tokens(slots, tok)

    def _op_prefix_prefill(self, suffix_bucket, prefix_bucket, tokens,
                           suffix_len, prefix_len, row, prior, slots, temps,
                           top_k, top_p, warm=False, wt=None):
        fn = self._prefix_prefill_fn(suffix_bucket, prefix_bucket)
        tok, k_pages, self.v_pages = fn(
            self.params, self._put(tokens), self._put(suffix_len),
            self._put(prefix_len),
            state_pool.at_slots(self.k_pages, slots, wt), self.v_pages,
            self._put(row), self._put(prior), self._next_key(warm),
            self._put(temps), self._put(top_k), self._put(top_p))
        self._keep_cache(k_pages, tokens.size)
        return self._op_keep_tokens(slots, tok)

    def _keep_cache(self, k_pages, rows: int, visits: int = 0) -> None:
        """Keep the cache a step returned. Where it carries counts of the
        router's choices (kvcache/state.py: held here, zero-compute), they
        are taken out and queued with the choices the step's ``rows`` made in
        all (booked behind a chunk's tokens, _land_chunk); and with them, of
        a decode chunk whose program reads the chosen experts alone, the
        held experts it read of the ``visits`` it made."""
        self.k_pages, held, zero, read = state_pool.take_counts(k_pages)
        self.telemetry.keep_pair_counts(
            held, zero, rows * self.bound.pairs_per_row, read, visits)

    def _op_mm_prefill(self, bucket, mm_bucket, tokens, seq_len, mm_pad,
                       pos_pad, row, slots, temps, top_k, top_p):
        fn = self._prefill_fn(bucket, mm_bucket)
        tok, self.k_pages, self.v_pages = fn(
            self.params, self._put(tokens), self._put(seq_len),
            self._put(mm_pad), self._put(pos_pad), self.k_pages,
            self.v_pages, self._put(row), self._next_key(False),
            self._put(temps), self._put(top_k), self._put(top_p))
        return self._op_keep_tokens(slots, tok)

    def _op_import(self, blocks_pad, k_pad, v_pad):
        self.k_pages, self.v_pages = self._jit_import(
            self.k_pages, self.v_pages, self._put(blocks_pad),
            self._put(k_pad), self._put(v_pad))

    def _batch_bucket(self, n: int) -> int:
        """Smallest power-of-two lane count covering n active slots, from
        two up: a few streams decode narrow instead of paying full-batch
        compute (compile cache stays bounded at log2(max_batch) decode
        variants). Never one lane: XLA rewrites a one-row page scatter as a
        dynamic-update-slice and re-lays-out both page buffers for it — two
        page-sized copies in and two out per chunk (4.5 GB of temporaries
        for Qwen3-4B at 16 × 2048, which the TPU compiler refuses on a
        16 GB chip; scripts/aot_rehearsal.py --decode-batches 1). Only an
        engine configured with max_batch 1 still has that one lane."""
        b = 2
        while b < n:
            b *= 2
        return min(b, self.cfg.max_batch)

    def _read_tokens(self, toks) -> np.ndarray:
        """Sampled tokens to the host: the loop's one blocking read of the
        device, for a chunk's tokens and for a prefill's first."""
        return np.asarray(toks)

    def _decode_lanes(self) -> list[tuple[int, _Slot]]:
        """The slots the next chunk decodes: every one that has a token, on
        the host or still on the device (a prefill dispatched this step or
        before, a chunk in flight), and that the host cannot tell will have
        ended before the chunk's first step: on max_tokens or the context
        limit inside the steps already dispatched. A stop token it cannot
        foresee: that lane's chunk is thrown away when it turns up."""
        return [(i, s) for i, s in enumerate(self.slots)
                if not (s is None or s.prefilling
                        or (s.req.kv_transfer_params
                            or {}).get("do_remote_decode")
                        or self._ends_in_flight(s))]

    def _ends_in_flight(self, s: _Slot) -> bool:
        """Whether the steps already dispatched for s, if any, reach its
        request's end on max_tokens or the context limit."""
        generated = len(s.generated) if s.pending_tok is None else 1
        return (generated + s.ahead >= s.req.max_tokens
                or s.position + s.ahead + 1 >= self.cfg.max_model_len)

    def _dispatch_chunk(self) -> _Chunk | None:
        """Dispatch the next chunk for the lanes that have one coming, on top
        of whatever is in flight; None when no lane has."""
        with self._phase("decode_prepare"):
            lanes = self._decode_lanes()
            if not lanes:
                return None
            B = self._batch_bucket(len(lanes))
            # Compact the lanes into the low rows; padding rows are nobody's
            # (token 0) and keep their block table at the trash block 0
            # (their KV writes land there).
            slots = np.full((B,), self.cfg.max_batch, np.int32)
            positions = np.zeros((B,), np.int32)
            tables = np.zeros((B, self.max_blocks_per_seq), np.int32)
            for lane, (i, s) in enumerate(lanes):
                slots[lane] = i
                positions[lane] = s.position + s.ahead
                tables[lane, : len(s.blocks)] = s.blocks
            reqs = [s.req for _, s in lanes]
            reqs += [_DUMMY_REQ] * (B - len(reqs))
            self.telemetry.batch_fill.set(
                len(lanes) / max(self.cfg.max_batch, 1))
            shape = f"{B}x{self.max_blocks_per_seq}"
            timed = ("decode", shape) in self._seen_op_shapes
            steps = self._chunk_steps(shape)
            args = dict(slots=slots, positions=positions, tables=tables,
                        steps=steps, **self._sample_np(reqs),
                        **self._window_tables(
                            [(s.blocks, s.position + s.ahead,
                              s.position + s.ahead + steps, False)
                             for _, s in lanes], B))
        t0 = self._clock()
        if self._inflight is None:
            self._begin_period()    # nothing ahead of it: its period is its own
        with self._phase("decode_dispatch"):
            toks = self._device_call(("decode",), args)
        self.telemetry.decode_chunks[
            "alone" if self._inflight is None else "ahead"].inc()
        fraction = next((name for name, div in SHORT_CHUNK_DIVS.items()
                         if steps == self.cfg.decode_chunk // div), "full")
        self.telemetry.decode_chunk_lengths[
            "full" if fraction == "full" else "short"].inc()
        self.telemetry.decode_chunk_fractions[fraction].inc()
        for _, s in lanes:
            s.ahead += steps
        behind, self._calls_since_chunk = self._calls_since_chunk, 0
        return _Chunk(toks=toks, steps=steps, lanes=lanes, t0=t0, timed=timed,
                      shape=shape, behind=behind)

    def _land_chunk(self, chunk: _Chunk) -> None:
        """Read a chunk's tokens (ONE readback a chunk) and book them."""
        with self._phase("decode_wait"):
            sampled = self._read_tokens(chunk.toks)[:chunk.steps]  # [n, B]
        now = self._clock()
        self.telemetry.book_pair_counts()
        if chunk.timed:
            # The chunk's own wall time: it could not start before the chunk
            # ahead of it was done, which the host saw at that one's
            # readback. The first call of a shape goes to the compile
            # histogram instead.
            period = now - max(chunk.t0, self._last_readback)
            self.telemetry.decode_step.observe(period)
            # A period in which some shape ran for the first time (the next
            # chunk's wider bucket, a prefill bucket) holds that program's
            # build: like the first call itself, it is nobody's stall.
            stall = None if self._period_first_call else self.stalls.note(
                period, self._period, loop_clock_s=now,
                lanes=len(chunk.lanes), batch=int(sampled.shape[1]),
                prefills=self._period_prefills)
            if stall is not None:
                log.warning("engine loop stall %s", json.dumps(stall))
            if not self._period_first_call:
                self._host_work.append(sum(
                    dt for phase, dt in self._period.items()
                    if phase not in ("decode_wait", "idle_wait")))
            if not (chunk.behind or self._period_first_call):
                # Nothing sat between it and the chunk before: the period
                # is the chunk's device time, what _hold_until reckons with.
                self._chunk_times.setdefault(
                    (chunk.shape, chunk.steps), collections.deque(
                        maxlen=HOLD_PERIODS)).append(period)
        self._last_readback = now
        self._begin_period()
        with self._phase("decode_book"):
            self._book_chunk(chunk.lanes, sampled)

    def _book_chunk(self, lanes: list[tuple[int, _Slot]],
                    sampled: np.ndarray) -> None:
        """Apply one chunk's sampled tokens [n, B] lane by lane, up to each
        request's stop condition. A lane's request either holds its slot
        still, or is retired (a successor holds the slot and this chunk has
        the request's last tokens), or ended while this chunk was in flight
        (a stop token in the chunk before, an abort)."""
        for lane, (i, s) in enumerate(lanes):
            s.ahead -= sampled.shape[0]
            if self.slots[i] is not s and not any(
                    r is s for _, r in self._retired):
                self.telemetry.decode_lanes_discarded.inc()
                continue
            for step in range(sampled.shape[0]):
                tok = int(sampled[step, lane])
                s.position += 1
                s.generated.append(tok)
                self.telemetry.generation_tokens.inc()
                if tok not in self._stop_ids(s.req):
                    self._emit(s, TokenEvent(
                        request_id=s.req.request_id, token_id=tok,
                        text=self.tokenizer.decode([tok]), is_first=not s.first_emitted,
                        completion_tokens=len(s.generated)))
                    s.first_emitted = True
                if self._maybe_finish_after_token(i, s, tok):
                    break  # stop/length hit mid-chunk; overshoot discarded

    def _stop_ids(self, req: EngineRequest) -> set[int]:
        stop_ids = set(req.stop_token_ids)
        if not req.ignore_eos:
            stop_ids.add(self.tokenizer.eos_id)
        return stop_ids

    def _maybe_finish_after_token(self, idx: int, s: _Slot, tok: int) -> bool:
        """Finish s, of slot idx, if ``tok`` was its last; whether it was."""
        stop_ids = self._stop_ids(s.req)
        reason = None
        if tok in stop_ids:
            reason = FinishReason.STOP
        elif len(s.generated) >= s.req.max_tokens:
            reason = FinishReason.LENGTH
        elif s.position + 1 >= self.cfg.max_model_len:
            reason = FinishReason.LENGTH
        if reason is not None:
            self._finish_slot(idx, s, reason)
        return reason is not None

    def _finish_slot(self, idx: int, s: _Slot, reason: FinishReason, *,
                     retain_for_transfer: bool = False, first_token: int | None = None):
        """End s, the request of slot idx: it holds the slot, or is retired
        and the slot is its successor's."""
        if self.slots[idx] is s:
            self.slots[idx] = None
        else:
            self._retired = [r for r in self._retired if r[1] is not s]
        kv_params = None
        if not retain_for_transfer:
            # Abort/error of a chunk-streaming prefill: reclaim the partial
            # export so the decode peer's next poll 404s and it falls back.
            self._drop_partial_export(s.req.request_id)
        if retain_for_transfer:
            # Stage the prefilled KV for pickup. Device path: gather the
            # slot's pages into fresh device arrays (the gather breaks the
            # alias to the donated page buffers, so blocks free immediately)
            # and register their unique shards with the transfer server for a
            # direct device-to-device pull (one descriptor per process — the
            # NIXL multi-rank analogue, connector_nixlv2.go:191-253). The
            # same arrays back the HTTP /kv route (converted lazily), so a
            # host-only decode peer still works against single-process
            # exporters. Block count pads to a power-of-two bucket (tail →
            # trash block 0) so gather here and scatter on the decode side
            # each compile at most log2(max_blocks)+1 variants.
            bucket = 1
            while bucket < len(s.blocks):
                bucket *= 2
            bucket = min(bucket, self.max_blocks_per_seq)
            padded = np.asarray(list(s.blocks)
                                + [0] * (bucket - len(s.blocks)), np.int32)
            tuid = uuid.uuid4().int & ((1 << 63) - 1)
            # Under dist the gather runs on EVERY process (global pages) and
            # each process registers its local shards — a leader-only gather
            # would deadlock the mesh, so it rides the replayed op stream.
            rec = self._device_call(("stage_kv",), dict(
                request_id=s.req.request_id, idx=padded, tuid=tuid,
                stream=bool((s.req.kv_transfer_params or {})
                            .get("stream_chunks"))))
            kv_params = {
                "remote_engine_id": self.engine_id,
                "remote_request_id": s.req.request_id,
                "remote_num_blocks": len(s.blocks),
                "remote_seq_len": s.position,
                "remote_first_token": first_token,
                "remote_host": self.cfg.host,
                "remote_port": self.cfg.port,
                # TLS exporters: the host-staged /kv fallback must dial the
                # right scheme (importers skip verification — pod-local
                # certs, same trust model as the transfer wires).
                "remote_scheme": ("https" if self.cfg.secure_serving
                                  else "http"),
            }
            with self._exports_lock:
                rec.update({
                    "num_blocks": len(s.blocks),  # real (un-padded) count
                    "seq_len": s.position,        # prompt tokens in cache
                    "first_token": first_token,
                })
            if "chunks_staged" in rec:
                # Chunk-streamed export: stage the tail chunk (including the
                # final partial block) and flip complete — AFTER the
                # metadata update above, so a puller observing complete=1
                # always finds seq_len/first_token stamped.
                self._finalize_chunk_export(rec, list(s.blocks))
            if rec.get("transfer_uuid") is not None:
                kv_params.update({
                    "transfer_uuid": rec["transfer_uuid"],
                    "kv_shape": [int(d) for d in rec["k"].shape],
                    "kv_dtype": str(rec["k"].dtype),
                })
                mesh = self._page_mesh()
                if mesh is None:
                    # Legacy single-device contract: one address, one
                    # [k, v] pull.
                    kv_params["transfer_address"] = self._transfer_address()
                else:
                    from .kv_shards import mesh_descriptor

                    kv_params["kv_mesh"] = mesh_descriptor(
                        mesh, pages.page_spec(mesh))
                    kv_params["transfer_shards"] = self._shard_addresses()
                    if self.kv_shard_wire is not None:
                        kv_params["shard_wire_addrs"] = (
                            self._shard_wire_addresses())
        with self._cond:
            self.allocator.free(s.blocks)
            self.telemetry.observe_allocator(self.allocator)
            self._cond.notify()  # capacity freed: wake admission
        if (self.kv_events is not None and s.block_hashes
                and not isinstance(self.allocator, PrefixCachingAllocator)):
            # With prefix caching the blocks PARK instead of freeing; 'removed'
            # is published at LRU eviction time (alloc path), not here.
            self.kv_events.removed(s.block_hashes)
        self.telemetry.running.set(sum(x is not None for x in self.slots))
        self.telemetry.request_success.labels(finished_reason=reason.value).inc()
        ev = TokenEvent(
            request_id=s.req.request_id, token_id=None, finish_reason=reason,
            kv_transfer_params=kv_params,
            prompt_tokens=len(s.req.prompt_token_ids),
            completion_tokens=len(s.generated))
        if retain_for_transfer and first_token is not None:
            ev.text = self.tokenizer.decode([first_token])
            ev.token_id = first_token
        self._emit(s, ev)


_DUMMY_REQ = EngineRequest(request_id="__pad__", prompt_token_ids=[0])
