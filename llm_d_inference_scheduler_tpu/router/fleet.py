"""Multi-process sharded gateway fleet: scheduling throughput past the GIL.

PR 5 moved scheduling cycles off the event loop, but its own benchmark
documents the ceiling: every worker thread shares one GIL, so *aggregate*
scheduling throughput under saturation churn cannot exceed one core
(docs/performance.md §Concurrency model, SCHED_OFFLOAD.json
``cycles_per_sec``). This module breaks that ceiling the way P/D-Serve
(arXiv:2408.08147) does at tens of thousands of devices — a fleet of
gateway processes in front of the shared pool:

- **N full gateway workers**, each its own process with its own event loop,
  scheduler pool, and flow-control shards, owning a disjoint shard of
  flows. They share the public listen port via ``SO_REUSEPORT`` (kernel
  connection balancing), or sit behind a thin hash-by-flow-id front
  balancer (``fleet.balancer: hash`` — the portable fallback, and the mode
  that gives *strict* flow→shard ownership).
- **Pool state replicates instead of multiplying**: one worker is the
  datalayer leader — the only process running the scrape + kv-event SSE
  pipeline — and publishes ``PoolSnapshot`` epochs over a unix-socket IPC
  stream (the copy-on-write snapshot from router/snapshot.py is already
  the serialization unit). Followers apply each frame as membership +
  scrape state + THE scheduling snapshot, so N workers impose 1× scrape
  load on every engine and a batch dispatched in any worker schedules
  against the same epoch it would have seen single-process. The staleness
  bound is the publish poll (= ``Datastore.SNAPSHOT_MIN_REFRESH_S``) on
  top of the soft-dirty window the single-process router already has.
  With ``fleet.replication`` (default on) the same stream carries the
  leader's engine-confirmed KvBlockIndex as sequence-numbered deltas +
  periodic full-index checkpoints, so precise-prefix scoring behaves
  identically in every shard (``router_kv_index_divergence`` ~0).
- **The leader is a role, not a process**: worker 0 leads at boot; when
  the leader dies the supervisor promotes the lowest-index live follower
  (``fleet.election``) onto a fresh snapshot socket, re-targets the
  remaining subscribers event-driven, and respawns the ex-leader as a
  follower — kill-the-leader is a measured drill (``make
  bench-fleet-chaos``), not an outage (docs/resilience.md §Fleet
  failover).
- **Observability fans back in**: the supervisor serves one merged
  ``/metrics`` (counters/histograms summed across workers, replicated pool
  gauges deduplicated, ``router_shard_*`` families labeled per shard) and
  one ``/debug/decisions`` / ``/debug/slo`` / ``/debug/transfers`` view
  that routes record lookups to the owning shard.

``fleet: {workers: 1}`` (the default) never enters this module — the
single-process router is bit-identical to the pre-fleet gateway.

Scaling is measured by ``make bench-scaleout`` → benchmarks/
SCHED_SCALEOUT.json: a 1/2/4-worker saturation-churn sweep with per-shard
picks bit-identical to a single-process run (``scheduling.pickSeed``).
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import logging
import multiprocessing
import os
import pickle
import shutil
import signal
import socket
import struct
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable, Iterable

import xxhash
from aiohttp import web
from prometheus_client import generate_latest
from prometheus_client.parser import text_string_to_metric_families

from . import snapwire
from .metrics import (
    FLEET_BALANCER_CONNECTIONS,
    FLEET_LEADER,
    FLEET_REGISTRY,
    FLEET_WORKERS,
    KV_INDEX_DIVERGENCE,
    KV_INDEX_RESYNCS,
    LEADER_ELECTIONS,
    SHARD_REQUESTS,
    SHARD_SNAPSHOT_EPOCH,
    SHARD_STATE,
    SHARD_UP,
    SNAPSHOT_FRAME_ERRORS,
)

log = logging.getLogger("router.fleet")

# Offset of the supervisor's admin port from the public data port when
# fleet.adminPort is not configured.
DEFAULT_ADMIN_OFFSET = 1000

# How long the supervisor waits for every worker's admin plane to answer
# before declaring the fleet up.
WORKER_READY_TIMEOUT_S = 30.0

# router_shard_state gauge encoding (docs/metrics.md): a deliberately
# scaled-in worker must be tellable from a crashed one on the wire.
_SHARD_STATE_NUM = {"down": 0.0, "up": 1.0, "retiring": 2.0, "retired": 3.0}

# Crash-restart budget per worker: a worker that keeps dying stops being
# restarted (the shard shows as down in router_shard_up instead of
# flapping forever).
MAX_WORKER_RESTARTS = 5


def flow_shard(flow_id: str, workers: int) -> int:
    """Stable flow→shard assignment shared by the front balancer and the
    bench's stream partitioner. xxh64, not ``hash()``: Python's string hash
    is salted per interpreter, and shard ownership must agree across
    processes and runs."""
    if workers <= 1:
        return 0
    return xxhash.xxh64_intdigest(flow_id.encode()) % workers


@dataclasses.dataclass
class FleetConfig:
    """The YAML ``fleet:`` section. ``workers: 1`` (default) is the
    single-process router, bit-identical to the pre-fleet gateway."""

    workers: int = 1
    balancer: str = "reuseport"   # reuseport | hash
    snapshot_ipc: bool = True     # leader publishes PoolSnapshot epochs
    admin_port: int | None = None  # default: data port + 1000
    # Snapshot frame encoding (ISSUE 19): "binary" ships the columnar
    # arrays raw (router/snapwire.py) with metrics-only delta frames;
    # "pickle" is the kill-switch back to whole-pool pickled entries.
    wire: str = "binary"
    # Confirmed-index replication (ISSUE 13a): the leader appends
    # sequence-numbered KvBlockIndex add/remove deltas + periodic
    # full-index checkpoints to the snapshot frame stream; followers apply
    # them so router_kv_index_divergence reads ~0 steady-state. `off` is
    # the kill-switch back to PR 8's speculative-only followers.
    replication: bool = True
    kv_checkpoint_s: float = 2.0
    # Leader re-election (ISSUE 13b): when the datalayer leader dies the
    # supervisor promotes the lowest-index live follower instead of
    # freezing every follower's pool view behind the leader's restart.
    election: bool = True

    @classmethod
    def from_spec(cls, spec: dict[str, Any] | None) -> "FleetConfig":
        spec = spec or {}
        balancer = str(spec.get("balancer", "reuseport"))
        if balancer not in ("reuseport", "hash"):
            raise ValueError(f"fleet.balancer must be 'reuseport' or 'hash', "
                             f"got {balancer!r}")
        wire = str(spec.get("wire", "binary"))
        if wire not in ("binary", "pickle"):
            raise ValueError(f"fleet.wire must be 'binary' or 'pickle', "
                             f"got {wire!r}")
        ckpt = float(spec.get("kvCheckpointS", 2.0))
        # Replica confirmed entries are renewed ONLY by checkpoints (the
        # engines' idempotent 1 s re-publication is deliberately
        # change-free, so steady state produces no delta traffic): a
        # cadence at or beyond the confirmed TTL would let every
        # follower's replica expire between checkpoints — divergence
        # sawtoothing to ~1.0 with no error pointing at the config. Half
        # the TTL keeps at least one renewal comfortably inside it.
        from .plugins.precise_prefix import KvBlockIndex

        ttl = KvBlockIndex.CONFIRMED_TTL_S
        if not 0 < ckpt <= ttl / 2:
            raise ValueError(
                f"fleet.kvCheckpointS must be in (0, {ttl / 2:g}] — the "
                f"checkpoint cadence renews follower replicas whose "
                f"confirmed TTL is {ttl:g}s")
        return cls(
            workers=max(1, int(spec.get("workers", 1))),
            balancer=balancer,
            snapshot_ipc=bool(spec.get("snapshotIpc", True)),
            admin_port=(int(spec["adminPort"])
                        if spec.get("adminPort") is not None else None),
            wire=wire,
            replication=bool(spec.get("replication", True)),
            kv_checkpoint_s=ckpt,
            election=bool(spec.get("election", True)))


@dataclasses.dataclass
class FleetWorkerSpec:
    """Per-worker identity handed to ``build_gateway`` (picklable: it rides
    the multiprocessing spawn)."""

    index: int
    workers: int
    role: str = "leader"           # leader | follower
    ipc_path: str | None = None    # None = every worker runs its own datalayer
    admin_host: str = "127.0.0.1"
    admin_port: int | None = None  # private per-worker admin listener
    reuse_port: bool = False
    # Confirmed-index replication on the snapshot stream (fleet.replication)
    replication: bool = True
    kv_checkpoint_s: float = 2.0
    # Snapshot frame encoding (fleet.wire): binary | pickle
    wire: str = "binary"
    # Shared per-fleet-run secret for the /fleet/promote + /fleet/retarget
    # control routes: the loopback peer check alone is spoofable through
    # the hash balancer's splice (the worker sees the balancer's loopback
    # address, not the client's).
    control_token: str | None = None
    # Supervisor fan-in admin port: lets the acting worker's autoscale
    # actuator reach POST /fleet/scale (0 = no supervisor, single-process).
    sup_admin_port: int = 0

    @property
    def runs_datalayer(self) -> bool:
        """Followers with snapshot IPC replicate pool state instead of
        scraping; everyone else (leader, or IPC disabled) runs the full
        scrape + SSE pipeline."""
        return self.role != "follower" or self.ipc_path is None


# ---------------------------------------------------------------------------
# Snapshot IPC: leader publishes PoolSnapshot epochs, followers apply them.
# Frames are tagged tuples on one length-prefixed pickle stream:
#   ("snap",   epoch, entries)  — pool snapshot (membership + scrape state)
#   ("kv",     seq,   deltas)   — confirmed KvBlockIndex deltas, deltas =
#                                 [(op, pod, hashes)], op: add|remove|drop,
#                                 seq strictly consecutive per publisher
#   ("kvsync", seq,   dump)     — periodic full confirmed-index checkpoint
#                                 ({pod: [hashes]}), the resync point for
#                                 mid-stream joiners and gap-detected
#                                 followers; seq re-anchors continuity
# ---------------------------------------------------------------------------

_FRAME_LEN = struct.Struct("!I")
_FRAME_MAX = 256 << 20  # sanity bound on one pickled pool frame


def _pack(frame: tuple) -> bytes:
    payload = pickle.dumps(frame, protocol=pickle.HIGHEST_PROTOCOL)
    return _FRAME_LEN.pack(len(payload)) + payload


class KvReplicationSource:
    """Leader-side tap on the precise scorer's engine-confirmed
    ``KvBlockIndex`` (router/plugins/precise_prefix.py): the index fires
    (op, pod, hashes) on confirmed-state *changes* — from the kv-event
    subscriber threads — and this buffer turns them into sequence-numbered
    delta batches the SnapshotPublisher drains on its poll cadence, plus
    the periodic full-index checkpoint a joiner resyncs from."""

    def __init__(self, index: Any):
        self.index = index
        self._lock = threading.Lock()
        self._pending: list[tuple[str, str, list[int]]] = []
        self.seq = 0  # last sequence number handed out
        index.set_delta_listener(self._on_delta)

    def _on_delta(self, op: str, pod: str, hashes: list[int]) -> None:
        with self._lock:
            self._pending.append((op, pod, hashes))

    def drain(self) -> tuple[int, list] | None:
        """(seq, deltas) for the next ``kv`` frame, or None when idle."""
        with self._lock:
            if not self._pending:
                return None
            batch, self._pending = self._pending, []
            self.seq += 1
            return self.seq, batch

    def checkpoint(self) -> tuple[int, dict[str, list[int]]]:
        """(seq, full confirmed dump) for a ``kvsync`` frame. Takes the
        lock so the dump's seq anchor can't race a concurrent drain()."""
        with self._lock:
            return self.seq, self.index.dump_confirmed()

    def close(self) -> None:
        self.index.set_delta_listener(None)


def _encode_frame(epoch: int, entries: list,
                  sanitizer: snapwire.AttrSanitizer) -> bytes:
    """Length-prefixed pickle of one snapshot epoch. Endpoint attributes
    can hold arbitrary producer outputs; anything unpicklable is dropped
    from the frame. Probe verdicts are memoized per (key, id(value)) by the
    sanitizer, so steady-state frames after a pickle failure cost one
    whole-frame attempt plus dict lookups — not a re-pickle of every
    attribute of every endpoint (and a picklable value under a
    once-poisoned key is no longer dropped forever)."""
    try:
        return _pack(("snap", epoch, entries))
    except Exception:
        sanitized = [
            (meta, metrics,
             {k: v for k, v in attrs.items() if sanitizer.probe(k, v)})
            for meta, metrics, attrs in entries]
        return _pack(("snap", epoch, sanitized))


class SnapshotPublisher:
    """Datalayer-leader side: poll the datastore's COW snapshot at the
    soft-dirty cadence and broadcast each NEW epoch to every connected
    follower over a unix socket. A follower that connects mid-stream gets
    the current epoch immediately (no warm-up gap).

    With a ``kv_source`` (fleet.replication, KvReplicationSource) the same
    poll also drains the engine-confirmed KvBlockIndex delta buffer into
    sequence-numbered ``kv`` frames and emits a full-index ``kvsync``
    checkpoint every ``kv_checkpoint_s`` — the resync point for mid-stream
    joiners (a restarted worker) and followers that detected a sequence
    gap. The checkpoint cadence is therefore the follower-divergence bound
    after any stream discontinuity."""

    def __init__(self, datastore: Any, path: str,
                 interval_s: float | None = None,
                 kv_source: KvReplicationSource | None = None,
                 kv_checkpoint_s: float = 2.0,
                 wire: str = "binary"):
        self.datastore = datastore
        self.path = path
        self.interval_s = (interval_s if interval_s is not None
                           else type(datastore).SNAPSHOT_MIN_REFRESH_S)
        self.kv_source = kv_source
        self.kv_checkpoint_s = kv_checkpoint_s
        self.wire = wire
        self._server: asyncio.AbstractServer | None = None
        self._task: asyncio.Task | None = None
        self._writers: list[asyncio.StreamWriter] = []
        self._frame: bytes | None = None       # last full frame (joiners)
        self._delta_frame: bytes | None = None  # latest delta on top of it
        self._epoch = -1
        self._next_checkpoint = 0.0
        self._sanitizer = snapwire.AttrSanitizer()
        # Delta-eligibility anchors: the full frame a delta may ride on.
        self._full_epoch = -1
        self._full_cols: Any = None
        self._full_blob: bytes | None = None

    async def start(self) -> None:
        with contextlib.suppress(OSError):
            os.unlink(self.path)
        self._server = await asyncio.start_unix_server(self._on_client,
                                                       path=self.path)
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None
        # Accepted connections first: on Python 3.12 wait_closed() waits for
        # every one of them, so closing them after it never happens.
        for w in self._writers:
            with contextlib.suppress(Exception):
                w.close()
        self._writers.clear()
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        if self.kv_source is not None:
            self.kv_source.close()
        with contextlib.suppress(OSError):
            os.unlink(self.path)

    async def _on_client(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter) -> None:
        # Mid-stream joiner warm-up: the cached full frame re-anchors
        # membership/attrs, then the latest delta (binary wire) brings the
        # metrics forward to the current epoch.
        if self._frame is not None:
            try:
                writer.write(self._frame)
                if self._delta_frame is not None:
                    writer.write(self._delta_frame)
                await writer.drain()
            except Exception:
                writer.close()
                return
        self._writers.append(writer)

    async def _run(self) -> None:
        try:
            while True:
                snap = self.datastore.snapshot()
                if snap.epoch != self._epoch:
                    # Mark the epoch consumed BEFORE encoding: a failed
                    # epoch is skipped (the next scrape mints a fresh one
                    # within ~one poll), not retried in a 10 ms log storm.
                    self._epoch = snap.epoch
                    try:
                        frame = self._encode_snapshot(snap)
                        await self._broadcast(frame)
                    except asyncio.CancelledError:
                        raise
                    except Exception:
                        # The publish loop must outlive one bad epoch
                        # (e.g. an unpicklable value inside a Metrics
                        # field, beyond the attribute sanitization): a
                        # silently-dead publisher would pin every follower
                        # to its last applied epoch — scheduling on
                        # ever-staler data with no error anywhere.
                        log.exception("snapshot publish failed for epoch "
                                      "%s; skipping it", snap.epoch)
                if self.kv_source is not None:
                    try:
                        await self._publish_kv()
                    except asyncio.CancelledError:
                        raise
                    except Exception:
                        log.exception("kv delta publish failed; skipping "
                                      "this batch")
                await asyncio.sleep(self.interval_s)
        except asyncio.CancelledError:
            pass

    def _encode_snapshot(self, snap: Any) -> bytes:
        """Encode one new epoch and refresh the joiner cache. Binary wire:
        when membership, metadata, and the (attrs, models) blob are all
        unchanged since the last full frame, the epoch ships as a
        metrics-only delta (absolute numeric columns over ``base_id``) —
        the steady-state frame whose size and apply cost don't scale with
        anything but the numeric columns themselves."""
        if self.wire != "binary":
            frame = _encode_frame(snap.epoch, snap.entries(),
                                  self._sanitizer)
            self._frame = frame
            return frame
        cols = snap.columns()
        blob = self._sanitizer.blob(cols.attrs, cols.models)
        prev = self._full_cols
        if (prev is not None and prev.n == cols.n
                and blob == self._full_blob
                and all(a is b for a, b in zip(prev.metas, cols.metas))):
            inner = snapwire.encode_delta(snap.epoch, self._full_epoch,
                                          cols.num)
            frame = _FRAME_LEN.pack(len(inner)) + inner
            self._delta_frame = frame
            return frame
        inner = snapwire.encode_full(snap.epoch, cols, blob)
        frame = _FRAME_LEN.pack(len(inner)) + inner
        self._frame = frame
        self._delta_frame = None
        self._full_epoch = snap.epoch
        self._full_cols = cols
        self._full_blob = blob
        return frame

    async def _publish_kv(self) -> None:
        """Drain pending confirmed-index deltas into one ``kv`` frame, and
        emit the periodic ``kvsync`` full-index checkpoint."""
        drained = self.kv_source.drain()
        if drained is not None:
            seq, deltas = drained
            await self._broadcast(_pack(("kv", seq, deltas)))
        now = time.monotonic()
        if now >= self._next_checkpoint:
            self._next_checkpoint = now + self.kv_checkpoint_s
            seq, dump = self.kv_source.checkpoint()
            await self._broadcast(_pack(("kvsync", seq, dump)))

    # A follower that stops draining (paused process, swap storm) must not
    # stall publication to the REST of the fleet: its drain is bounded, and
    # on timeout the writer is dropped — the follower reconnects and gets
    # the current frame fresh.
    DRAIN_TIMEOUT_S = 1.0

    async def _broadcast(self, frame: bytes) -> None:
        # Remove ONLY failed writers, never reassign the list wholesale:
        # each drain() is a yield point where _on_client may append a
        # newly-connected follower, and a snapshot-then-replace would drop
        # it — an open connection that never receives another epoch.
        for w in list(self._writers):
            try:
                w.write(frame)
                await asyncio.wait_for(w.drain(), timeout=self.DRAIN_TIMEOUT_S)
            except Exception:
                with contextlib.suppress(Exception):
                    w.close()
                with contextlib.suppress(ValueError):
                    self._writers.remove(w)


class SnapshotSubscriber:
    """Follower side: connect to the leader's snapshot socket (retrying —
    the leader may still be booting, or restarting) and apply each frame
    via ``Datastore.apply_remote_snapshot``.

    With a ``kv_index`` (fleet.replication, the follower's own
    KvBlockIndex) the subscriber also applies the leader's confirmed-index
    ``kv`` delta frames and ``kvsync`` checkpoints. Continuity is tracked
    by sequence number *within a connection*: deltas apply from the first
    frame seen (adds are idempotent, removes of absent hashes harmless —
    the base is healed by the next checkpoint), but once a GAP is detected
    the follower stops applying deltas (``router_kv_index_resyncs_total``)
    and waits for the next checkpoint rather than mutating an uncertain
    base. A reconnect or a leader change resets continuity the same way,
    so the divergence window after any discontinuity is bounded by the
    publisher's checkpoint cadence.

    ``retarget(path)`` is the promotion notice (ISSUE 13 satellite): the
    supervisor elected a new leader on a fresh socket, and the subscriber
    must re-aim NOW — including mid-backoff against the dead socket, which
    would otherwise be retried for up to RETRY_MAX_S more."""

    RETRY_MAX_S = 5.0  # backoff ceiling for consecutive apply failures

    def __init__(self, datastore: Any, path: str, retry_s: float = 0.25,
                 kv_index: Any = None):
        self.datastore = datastore
        self.path = path
        self.retry_s = retry_s
        self.kv_index = kv_index
        self._task: asyncio.Task | None = None
        self.applied_epoch = 0
        self.applied_kv_seq: int | None = None
        self.kv_dirty = False  # gap detected: deltas parked until kvsync
        self._consecutive_failures = 0
        self._retargeted: asyncio.Event | None = None
        self._cur_writer: asyncio.StreamWriter | None = None

    def start(self) -> None:
        self._retargeted = asyncio.Event()
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._task
            self._task = None

    def retarget(self, path: str) -> None:
        """Promotion notice: aim at the new leader's socket immediately —
        wake a pending backoff sleep and cut any connection still open to
        the old (dead) leader."""
        self.path = path
        self._consecutive_failures = 0
        if self._retargeted is not None:
            self._retargeted.set()
        w = self._cur_writer
        if w is not None:
            with contextlib.suppress(Exception):
                w.close()

    async def _run(self) -> None:
        try:
            while True:
                try:
                    reader, writer = await asyncio.open_unix_connection(
                        path=self.path)
                except (OSError, ConnectionError):
                    await self._sleep(self.retry_s)
                    continue
                self._cur_writer = writer
                # Fresh connection = fresh delta continuity: deltas apply
                # optimistically from the first frame (a gap parked on the
                # PREVIOUS connection does not carry over), full fidelity
                # returns at the next checkpoint.
                self.applied_kv_seq = None
                self.kv_dirty = False
                try:
                    await self._consume(reader)
                except (asyncio.IncompleteReadError, ConnectionError,
                        OSError):
                    pass  # leader restart / stream cut: reconnect quietly
                except Exception:
                    # A bad frame (unpicklable-by-reference value, shape
                    # drift across versions) must not kill the subscriber
                    # silently — that would pin this follower to its last
                    # applied epoch forever. Log and reconnect. The
                    # publisher re-sends the CURRENT frame on reconnect,
                    # so a SYSTEMATIC failure (e.g. mixed builds in a
                    # rolling upgrade) would tight-loop full-pool
                    # transfers + tracebacks — back off exponentially on
                    # consecutive apply failures instead.
                    self._consecutive_failures += 1
                    log.exception("snapshot frame failed to apply "
                                  "(%d consecutive); reconnecting",
                                  self._consecutive_failures)
                finally:
                    self._cur_writer = None
                    with contextlib.suppress(Exception):
                        writer.close()
                await self._sleep(min(
                    self.retry_s * (2 ** self._consecutive_failures),
                    self.RETRY_MAX_S))
        except asyncio.CancelledError:
            pass

    async def _sleep(self, delay: float) -> None:
        """Backoff that a retarget() can interrupt: a promotion notice
        must not wait out an exponential backoff aimed at a socket that
        will never return."""
        ev = self._retargeted
        if ev is None:
            await asyncio.sleep(delay)
            return
        with contextlib.suppress(asyncio.TimeoutError):
            await asyncio.wait_for(ev.wait(), timeout=delay)
        ev.clear()

    async def _consume(self, reader: asyncio.StreamReader) -> None:
        while True:
            header = await reader.readexactly(_FRAME_LEN.size)
            (length,) = _FRAME_LEN.unpack(header)
            if not 0 < length <= _FRAME_MAX:
                raise ConnectionError(f"bad snapshot frame length {length}")
            payload = await reader.readexactly(length)
            if snapwire.is_binary_frame(payload):
                # Binary frames carry their own magic/version/checksum: a
                # bad one is counted and SKIPPED, never a crash or even a
                # reconnect — the outer length prefix already re-aligned
                # the stream past it.
                self._handle_binary(payload)
                self._consecutive_failures = 0
                continue
            frame = pickle.loads(payload)
            kind = frame[0]
            if kind == "snap":
                _, epoch, entries = frame
                self.datastore.apply_remote_snapshot(epoch, entries)
                self.applied_epoch = epoch
            elif kind == "kv":
                self._apply_kv_deltas(frame[1], frame[2])
            elif kind == "kvsync":
                self._apply_kv_checkpoint(frame[1], frame[2])
            else:
                raise ConnectionError(f"unknown frame kind {kind!r}")
            self._consecutive_failures = 0

    def _handle_binary(self, payload: bytes) -> None:
        try:
            decoded = snapwire.decode(payload)
        except snapwire.FrameError as e:
            SNAPSHOT_FRAME_ERRORS.labels(reason=e.reason).inc()
            log.warning("snapshot IPC: skipping bad binary frame (%s)", e)
            return
        if decoded[0] == "full":
            _, epoch, cols = decoded
            self.datastore.apply_remote_columns(epoch, cols)
            self.applied_epoch = epoch
        else:
            _, epoch, base_id, num = decoded
            # False = the delta's base full frame isn't what's installed
            # (e.g. frames raced a reconnect): not corruption — drop it,
            # the next full re-anchors.
            if self.datastore.apply_remote_delta(epoch, base_id, num):
                self.applied_epoch = epoch
            else:
                log.debug("snapshot IPC: delta for base %d does not match "
                          "installed columns; dropped", base_id)

    def _apply_kv_deltas(self, seq: int, deltas: list) -> None:
        if self.kv_index is None:
            return
        expected = self.applied_kv_seq
        self.applied_kv_seq = seq
        if expected is not None and seq != expected + 1 and not self.kv_dirty:
            # Dropped/reordered frame: applying further deltas would
            # mutate an uncertain base. Park until the next checkpoint.
            self.kv_dirty = True
            KV_INDEX_RESYNCS.inc()
            log.warning("kv delta gap (expected seq %d, got %d); waiting "
                        "for the next checkpoint", expected + 1, seq)
        if self.kv_dirty:
            return
        for op, pod, hashes in deltas:
            if op == "add":
                self.kv_index.add(pod, hashes)
            elif op == "remove":
                self.kv_index.remove(pod, hashes)
            elif op == "drop":
                self.kv_index.drop_pod(pod)

    def _apply_kv_checkpoint(self, seq: int, dump: dict) -> None:
        if self.kv_index is None:
            return
        self.kv_index.apply_checkpoint(dump)
        self.applied_kv_seq = seq
        self.kv_dirty = False


# ---------------------------------------------------------------------------
# Merged observability: one /metrics, /debug/decisions, /debug/slo,
# /debug/transfers across shards.
# ---------------------------------------------------------------------------

# Gauge families the merge must NOT sum — two classes, same max rule:
# - replicated pool state (snapshot IPC / same engines): every worker
#   reports the same value, so summing multiplies it by the worker count
#   (max == the shared value; under IPC lag, the freshest worker's view);
# - bounded per-worker gauges — ratios and enums: summing two workers'
#   0.9 SLO attainment to 1.8, or two open breakers (state 2) to 4,
#   produces values outside the family's domain. Max is the conservative
#   worst/best-state view; the REQUEST-WEIGHTED attainment merge (the
#   accurate one) is what the supervisor's /debug/slo serves.
MAX_MERGED_GAUGES = {
    "inference_pool_ready_pods",
    "inference_pool_average_kv_cache_utilization",
    "inference_pool_average_queue_size",
    "router_snapshot_epoch",
    "router_slo_attainment",
    "router_endpoint_circuit_breaker_state",
    # Burn rate is a ratio: two workers each burning 5x must read as 5x,
    # not 10x (the request-weighted view is the merged /debug/timeline's
    # job). RSS/FDs stay summed — fleet-total footprint is the useful
    # aggregate for per-worker process gauges.
    "router_slo_burn_rate",
}


def _escape_label(v: str) -> str:
    return v.replace("\\", r"\\").replace("\n", r"\n").replace('"', r'\"')


def _escape_help(v: str) -> str:
    return v.replace("\\", r"\\").replace("\n", r"\n")


def merge_parsed(families_per_worker: list[list[Any]]) -> str:
    """Merge parsed Prometheus metric families from N workers into one
    exposition: counters/histograms/summaries sum sample-wise, replicated
    pool gauges take max, ``_created`` timestamps take min (earliest
    birth), everything keyed by (sample name, labels) so per-model /
    per-endpoint children merge correctly. One HELP/TYPE block per family —
    the duplicate-family lint in scripts/verify_metrics.py holds on the
    output."""
    order: list[str] = []
    fams: dict[str, Any] = {}
    values: dict[str, dict[tuple, float]] = {}
    for families in families_per_worker:
        for fam in families:
            if fam.name not in fams:
                fams[fam.name] = fam
                values[fam.name] = {}
                order.append(fam.name)
            acc = values[fam.name]
            replicated = (fam.type == "gauge"
                          and fam.name in MAX_MERGED_GAUGES)
            for s in fam.samples:
                key = (s.name, tuple(sorted(s.labels.items())))
                prev = acc.get(key)
                if prev is None:
                    acc[key] = s.value
                elif s.name.endswith("_created"):
                    acc[key] = min(prev, s.value)
                elif replicated:
                    acc[key] = max(prev, s.value)
                else:
                    acc[key] = prev + s.value
    out: list[str] = []
    for name in order:
        fam = fams[name]
        ftype = "untyped" if fam.type == "unknown" else fam.type
        # Classic text format spells counter families WITH the _total
        # suffix on the HELP/TYPE lines (the parser strips it from
        # fam.name); re-append it so the merged exposition round-trips.
        decl = name + "_total" if fam.type == "counter" else name
        out.append(f"# HELP {decl} {_escape_help(fam.documentation)}")
        out.append(f"# TYPE {decl} {ftype}")
        for (sname, labels), value in values[name].items():
            if labels:
                lbl = ",".join(f'{k}="{_escape_label(str(v))}"'
                               for k, v in labels)
                out.append(f"{sname}{{{lbl}}} {value}")
            else:
                out.append(f"{sname} {value}")
    return "\n".join(out) + "\n"


def merge_expositions(texts: Iterable[str]) -> str:
    """Text-level convenience wrapper over ``merge_parsed``."""
    return merge_parsed([list(text_string_to_metric_families(t))
                         for t in texts])


def _merge_err(target: dict[str, Any], err: dict[str, Any]) -> None:
    """Merge one predictor-error rollup ({n, mae_ms, mean_signed_ms}) into
    target, n-weighted."""
    n0, n1 = target.get("n", 0), err.get("n", 0)
    if not n1:
        return
    if not n0:
        target.update(err)
        return
    n = n0 + n1
    target["mae_ms"] = round((target["mae_ms"] * n0 + err["mae_ms"] * n1) / n, 3)
    target["mean_signed_ms"] = round(
        (target["mean_signed_ms"] * n0 + err["mean_signed_ms"] * n1) / n, 3)
    target["n"] = n


def _merge_agg(target: dict[str, Any], agg: dict[str, Any]) -> None:
    """Merge one SLO attainment/goodput accumulator render (slo.py _Agg)
    into target: counts sum, attainment recomputed from the summed counts,
    predictor errors n-weighted."""
    for k in ("requests", "slo_met", "shed", "output_tokens",
              "goodput_tokens"):
        target[k] = target.get(k, 0) + agg.get(k, 0)
    served = target.get("requests", 0) - target.get("shed", 0)
    target["attainment"] = (round(target.get("slo_met", 0) / served, 4)
                            if served > 0 else None)
    if "predictor" in agg:
        tp = target.setdefault("predictor", {"ttft": {"n": 0},
                                             "tpot": {"n": 0}})
        for kind in ("ttft", "tpot"):
            _merge_err(tp.setdefault(kind, {"n": 0}),
                       agg["predictor"].get(kind, {"n": 0}))


def shard_index_divergence(leader: dict[str, Any],
                           follower: dict[str, Any]) -> float:
    """Fraction of the leader's engine-CONFIRMED KvBlockIndex blocks a
    follower's index view (replicated confirmed entries + short-TTL
    speculative stamps) cannot account for, compared pod by pod on the
    /debug/kv payloads. 0 = the follower's view covers everything the
    leader confirmed (or the leader has confirmed nothing yet); 1 = no
    overlap at all. Counts, not contents — the stamp SETS are
    process-local — so this is a coverage bound. With
    ``fleet.replication`` on it reads ~0 steady-state (followers apply the
    leader's delta stream); excursions mark discontinuities — a mid-stream
    joiner before its first checkpoint, or ``replication: off`` (PR 8's
    speculative-only followers, the state PR 10 measured)."""
    leader_pods = leader.get("pods") or {}
    follower_pods = follower.get("pods") or {}
    confirmed = covered = 0
    for pod, row in leader_pods.items():
        n = int(row.get("confirmed_blocks") or 0)
        if n <= 0:
            continue
        confirmed += n
        frow = follower_pods.get(pod) or {}
        known = (int(frow.get("confirmed_blocks") or 0)
                 + int(frow.get("speculative_blocks") or 0))
        covered += min(known, n)
    if confirmed <= 0:
        return 0.0
    return round(1.0 - covered / confirmed, 4)


def merge_kv(docs: list[tuple[int, dict[str, Any]]],
             leader_shard: int = 0) -> dict[str, Any]:
    """Fleet /debug/kv: shard-annotated per-worker snapshots, summed stamp/
    join totals, n-weighted prediction MAE, and the per-shard divergence
    gauge versus the datalayer leader's confirmed index
    (``leader_shard`` — shard 0 until a re-election moves it)."""
    out: dict[str, Any] = {
        "workers": len(docs),
        "enabled": any(d.get("enabled") for _, d in docs),
        "leader_shard": leader_shard,
        "predicted_stamps": 0,
        "confirmed_joins": 0,
        "prediction": {"n": 0},
        "prediction_ratio": {"n": 0},
        "shards": [],
        "index_divergence": {},
    }
    leader = next((d for shard, d in docs if shard == leader_shard), None)
    n_tot = sum_abs = sum_signed = 0.0
    rn_tot = rsum_abs = rsum_signed = 0.0
    # Prefill-classifier accuracy: confusion counts sum across shards;
    # precision/recall are recomputed from the sums, never averaged.
    cls_counts = {"skip_correct": 0, "skip_wrong": 0,
                  "keep_missed_skip": 0, "keep_necessary": 0}
    for shard, doc in docs:
        for k, v in ((doc.get("classifier") or {}).get("counts")
                     or {}).items():
            if k in cls_counts:
                cls_counts[k] += int(v)
        pred = doc.get("prediction") or {}
        n = pred.get("n", 0)
        if n:
            n_tot += n
            sum_abs += pred.get("mae_blocks", 0.0) * n
            sum_signed += pred.get("mean_signed_blocks", 0.0) * n
        rpred = doc.get("prediction_ratio") or {}
        rn = rpred.get("n", 0)
        if rn:
            rn_tot += rn
            rsum_abs += rpred.get("mae_ratio", 0.0) * rn
            rsum_signed += rpred.get("mean_signed_ratio", 0.0) * rn
        out["predicted_stamps"] += doc.get("predicted_stamps", 0)
        out["confirmed_joins"] += doc.get("confirmed_joins", 0)
        div = (0.0 if shard == leader_shard or leader is None
               else shard_index_divergence(leader, doc))
        out["index_divergence"][str(shard)] = div
        KV_INDEX_DIVERGENCE.labels(str(shard)).set(div)
        out["shards"].append({"shard": shard, **doc,
                              "index_divergence": div})
    if n_tot:
        out["prediction"] = {"n": int(n_tot),
                             "mae_blocks": round(sum_abs / n_tot, 3),
                             "mean_signed_blocks": round(
                                 sum_signed / n_tot, 3)}
    if rn_tot:
        out["prediction_ratio"] = {"n": int(rn_tot),
                                   "mae_ratio": round(rsum_abs / rn_tot, 4),
                                   "mean_signed_ratio": round(
                                       rsum_signed / rn_tot, 4)}
    tp, fp = cls_counts["skip_correct"], cls_counts["skip_wrong"]
    fn = cls_counts["keep_missed_skip"]
    cls_doc: dict[str, Any] = {"judged": sum(cls_counts.values()),
                               "counts": cls_counts}
    if tp + fp:
        cls_doc["precision"] = round(tp / (tp + fp), 4)
    if tp + fn:
        cls_doc["recall"] = round(tp / (tp + fn), 4)
    out["classifier"] = cls_doc
    return out


def merge_transfers(docs: list[tuple[int, dict[str, Any]]]) -> dict[str, Any]:
    """Fleet /debug/transfers: one row per (prefill, decode) pair across
    shards. The same pair observed by multiple shards used to render as
    duplicate shard-annotated rows; here the EWMAs merge n-weighted by each
    shard's measured pull count (the merge_kv precedent), pull/byte totals
    sum, ``last_unix`` keeps the freshest observation, and ``shards`` lists
    every worker that contributed. ``ewma_mb_per_s`` is recomputed from the
    merged EWMAs, never averaged."""
    merged: dict[tuple[str, str], dict[str, Any]] = {}
    weights: dict[tuple[str, str], dict[str, float]] = {}
    for shard, doc in docs:
        for row in doc.get("pairs") or []:
            key = (row.get("prefill", ""), row.get("decode", ""))
            out = merged.get(key)
            if out is None:
                out = merged[key] = {"prefill": key[0], "decode": key[1],
                                     "pulls": 0, "bytes_total": 0,
                                     "last_unix": 0.0, "shards": []}
                weights[key] = {"pull": 0.0, "exposed": 0.0, "bytes": 0.0,
                                "prefill": 0.0}
            w = weights[key]
            pulls = int(row.get("pulls") or 0)
            out["pulls"] += pulls
            out["bytes_total"] += int(row.get("bytes_total") or 0)
            out["last_unix"] = max(out["last_unix"],
                                   float(row.get("last_unix") or 0.0))
            out["shards"].append(shard)
            # EWMA fields weight by the shard's measured pull count; a
            # prefill-only row (streamed responses carry no engine pull
            # stats, so pulls == 0) still contributes its prefill EWMA at
            # weight 1.
            pw = float(max(pulls, 1))
            for field, wkey, wval in (("ewma_pull_ms", "pull", float(pulls)),
                                      ("exposed_ms", "exposed", float(pulls)),
                                      ("ewma_bytes", "bytes", float(pulls)),
                                      ("ewma_prefill_ms", "prefill", pw)):
                v = row.get(field)
                if v is None or wval <= 0:
                    continue
                prev_w = w[wkey]
                prev_v = out.get(field)
                out[field] = (v if prev_v is None or prev_w == 0
                              else (prev_v * prev_w + v * wval)
                              / (prev_w + wval))
                w[wkey] = prev_w + wval
    pairs = []
    for out in merged.values():
        for field in ("ewma_pull_ms", "exposed_ms", "ewma_bytes",
                      "ewma_prefill_ms"):
            if out.get(field) is not None:
                out[field] = round(out[field], 3)
        if out.get("ewma_bytes") is not None and out.get("ewma_pull_ms"):
            out["ewma_mb_per_s"] = round(
                out["ewma_bytes"] / out["ewma_pull_ms"] / 1e3, 3)
        out["shards"] = sorted(set(out["shards"]))
        pairs.append(out)
    pairs.sort(key=lambda r: (r["prefill"], r["decode"]))
    return {"workers": len(docs), "pairs": pairs}


def merge_slo(docs: list[dict[str, Any]]) -> dict[str, Any]:
    """Fleet /debug/slo: the sum of the per-worker ledgers — totals,
    per-endpoint and per-band rollups, miss/shed reason tallies — with
    ratios recomputed from the summed counts (never averaged)."""
    out: dict[str, Any] = {
        "enabled": any(d.get("enabled") for d in docs),
        "workers": len(docs),
        "totals": {},
        "endpoints": {},
        "bands": {},
        "workloads": {},
        "miss_reasons": {},
        "shed_reasons": {},
    }
    since = [d["since_unix"] for d in docs if d.get("since_unix")]
    if since:
        out["since_unix"] = min(since)
        out["window_s"] = round(time.time() - out["since_unix"], 1)
    for doc in docs:
        _merge_agg(out["totals"], doc.get("totals") or {})
        for ep, agg in (doc.get("endpoints") or {}).items():
            _merge_agg(out["endpoints"].setdefault(ep, {}), agg)
        for band, agg in (doc.get("bands") or {}).items():
            _merge_agg(out["bands"].setdefault(band, {}), agg)
        for wl, agg in (doc.get("workloads") or {}).items():
            _merge_agg(out["workloads"].setdefault(wl, {}), agg)
        for key in ("miss_reasons", "shed_reasons"):
            for reason, n in (doc.get(key) or {}).items():
                out[key][reason] = out[key].get(reason, 0) + n
    if out["totals"].get("output_tokens"):
        out["totals"]["goodput_ratio"] = round(
            out["totals"].get("goodput_tokens", 0)
            / out["totals"]["output_tokens"], 4)
    return out


class FleetAdmin:
    """The supervisor's fan-in admin plane, separable from process
    management (tests drive it against stub workers): merged /metrics and
    the /debug record lookups routed to the owning shard.

    With a ``timeline`` config the admin also runs the SUPERVISOR side of
    the fleet flight recorder (router/timeline.py): a grid-aligned poll
    that derives the per-shard KV-index divergence series — a worker
    cannot see its own divergence, only the fan-in can compute it — and
    evaluates the divergence bound rule into supervisor-owned incidents.
    The merged ``/debug/timeline`` then carries the worker rings bucketed
    by wall clock (gaps marked when a shard was down) beside the
    supervisor's divergence series, so a kill-the-leader chaos run reads
    as one timeline with the excursion and the incident that recorded
    it."""

    def __init__(self, worker_admin: list[tuple[str, int]], *,
                 host: str = "127.0.0.1", port: int = 9081,
                 worker_alive: Callable[[int], bool] | None = None,
                 timeline: Any = None,
                 fleet_state: Callable[[], dict[str, Any]] | None = None,
                 worker_state: Callable[[int], str] | None = None,
                 scale_fn: Callable[[str, int | None], Any] | None = None,
                 control_token: str | None = None):
        from .timeline import IncidentRecorder, TimelineConfig

        self.worker_admin = worker_admin
        self.host, self.port = host, port
        self.worker_alive = worker_alive or (lambda i: True)
        # Per-shard lifecycle state for health/metrics: up | down |
        # retiring | retired. Stubs derive it from liveness alone — a
        # supervisor that scales workers in passes the real state so a
        # deliberately-retired shard doesn't read as an outage.
        self.worker_state = worker_state or (
            lambda i: "up" if self.worker_alive(i) else "down")
        # Supervisor scale hooks for POST /fleet/scale ("retire"/"restore"
        # → shard index or None on refusal). Absent on stubs → 501.
        self.scale_fn = scale_fn
        self.control_token = control_token
        # Supervisor role/election state for the fan-in surfaces: leader
        # shard (divergence is measured against it), election count,
        # per-worker restart tallies. Stubs default to the static PR 8
        # shape (shard 0 leads, no elections).
        self.fleet_state = fleet_state or (lambda: {"leader": 0,
                                                    "elections": 0})
        self.timeline_cfg = timeline or TimelineConfig()
        self._sup_ring: "deque[dict[str, Any]]" = deque(
            maxlen=self.timeline_cfg.ring_capacity)
        self._last_kv_doc: dict[str, Any] | None = None
        self._sup_incidents = IncidentRecorder(
            self.timeline_cfg,
            kv_snapshot_fn=lambda: self._last_kv_doc or {})
        self._timeline_task: asyncio.Task | None = None
        self.app = web.Application()
        self.app.add_routes([
            web.get("/metrics", self.metrics),
            web.get("/health", self.health),
            web.get("/debug/fleet", self.fleet_view),
            web.get("/debug/decisions", self.decisions),
            web.get("/debug/decisions/{request_id}", self.decision_detail),
            web.get("/debug/slo", self.slo),
            web.get("/debug/transfers", self.transfers),
            web.get("/debug/tails", self.tails),
            web.get("/debug/kv", self.kv),
            web.get("/debug/shadow", self.shadow),
            web.get("/debug/traces", self.traces),
            web.get("/debug/timeline", self.timeline),
            web.get("/debug/incidents", self.incidents),
            web.get("/debug/rebalance", self.rebalance),
            web.get("/debug/forecast", self.forecast),
            web.get("/debug/autoscale", self.autoscale),
            web.get("/debug/config", self.config),
            web.post("/fleet/scale", self.scale),
        ])
        self._runner: web.AppRunner | None = None
        self._session = None
        # Per-shard request totals already credited to SHARD_REQUESTS (the
        # counter advances by scrape deltas; a worker restart resets its
        # own totals, so negative deltas clamp to 0).
        self._credited: dict[int, float] = {}
        # Last successfully parsed exposition per shard: an unreachable
        # worker (restart, slow scrape) must not make the merged *_total
        # counters DIP and recover — Prometheus reads that as a counter
        # reset and rate()/increase() spike on every fleet series. Serving
        # the stale families keeps the merge monotonic; router_shard_up
        # says which shard the staleness belongs to.
        self._last_families: dict[int, list] = {}

    async def start(self) -> None:
        import aiohttp

        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=5.0))
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        if self.timeline_cfg.enabled and self.worker_admin:
            self._timeline_task = asyncio.get_running_loop().create_task(
                self._timeline_loop())

    async def stop(self) -> None:
        if self._timeline_task is not None:
            self._timeline_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._timeline_task
            self._timeline_task = None
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
        if self._session is not None:
            await self._session.close()
            self._session = None

    async def _timeline_loop(self) -> None:
        """Supervisor half of the flight recorder: one grid-aligned tick
        deriving the per-shard divergence series from the /debug/kv
        fan-in (merge_kv also sets the router_kv_index_divergence gauges)
        and evaluating the divergence bound rule into supervisor-owned
        incidents."""
        tick = self.timeline_cfg.tick_s
        try:
            while True:
                now = time.time()
                next_t = (int(now / tick) + 1) * tick
                await asyncio.sleep(max(next_t - now, 0.0))
                with contextlib.suppress(Exception):
                    await self._timeline_tick()
        except asyncio.CancelledError:
            pass

    async def _timeline_tick(self) -> None:
        from .timeline import RULE_DIVERGENCE

        results = await self._fan_out("/debug/kv")
        docs = [(shard, doc)
                for shard, (status, doc) in enumerate(results)
                if status == 200 and isinstance(doc, dict)]
        if not docs:
            return
        merged = merge_kv(docs, leader_shard=int(
            self.fleet_state().get("leader", 0)))
        self._last_kv_doc = merged
        div = {str(k): v
               for k, v in (merged.get("index_divergence") or {}).items()}
        # A shard the supervisor knows exists but that did not answer is
        # FULLY diverged for the series: its index view covers nothing
        # while it is down (a killed leader, a crashed follower mid-boot),
        # which is exactly the excursion a kill-the-leader chaos run must
        # record. /debug/kv itself keeps reporting responding shards only;
        # shards_responding says which values were measured vs imputed.
        responding = {shard for shard, _ in docs}
        for shard in range(len(self.worker_admin)):
            if shard not in responding:
                div[str(shard)] = 1.0
                KV_INDEX_DIVERGENCE.labels(str(shard)).set(1.0)
        sample: dict[str, Any] = {
            "t_unix": time.time(),
            "kv_index_divergence": div,
            "kv_index_divergence_max": max(div.values(), default=0.0),
            "shards_responding": sorted(responding),
        }
        self._sup_ring.append(sample)
        tripped: dict[str, str] = {}
        cfg = self.timeline_cfg
        if (cfg.divergence_max > 0
                and sample["kv_index_divergence_max"] > cfg.divergence_max):
            tripped[RULE_DIVERGENCE] = (
                f"max shard divergence "
                f"{sample['kv_index_divergence_max']:.4f} > "
                f"{cfg.divergence_max}")
        self._sup_incidents.observe(
            tripped, sample,
            lambda: list(self._sup_ring)[-cfg.context_ticks - 1:-1])

    async def _fetch(self, shard: int, path: str) -> tuple[int, Any]:
        """(status, json-or-text) from one worker's admin plane; (0, None)
        when the worker is unreachable."""
        host, port = self.worker_admin[shard]
        try:
            async with self._session.get(
                    f"http://{host}:{port}{path}") as resp:
                if "json" in (resp.headers.get("content-type") or ""):
                    return resp.status, await resp.json()
                return resp.status, await resp.text()
        except Exception:
            return 0, None

    async def _fan_out(self, path: str) -> list[tuple[int, Any]]:
        return await asyncio.gather(
            *[self._fetch(i, path) for i in range(len(self.worker_admin))])

    async def metrics(self, request: web.Request) -> web.Response:
        results = await self._fan_out("/metrics")
        parsed: list[list[Any]] = []
        for shard, (status, text) in enumerate(results):
            up = status == 200 and isinstance(text, str)
            SHARD_UP.labels(str(shard)).set(1.0 if up else 0.0)
            SHARD_STATE.labels(str(shard)).set(
                _SHARD_STATE_NUM.get(self.worker_state(shard), 0.0))
            if up:
                families = list(text_string_to_metric_families(text))
                self._last_families[shard] = families
                self._note_shard_stats(shard, families)
            else:
                # Monotonicity over freshness for a missing shard: merge
                # its last-seen families so fleet counters don't dip and
                # "reset" (see _last_families).
                families = self._last_families.get(shard)
            if families:
                parsed.append(families)
        body = merge_parsed(parsed) + generate_latest(FLEET_REGISTRY).decode()
        return web.Response(text=body, content_type="text/plain",
                            charset="utf-8")

    def _note_shard_stats(self, shard: int, families: list[Any]) -> None:
        """Derive the per-shard families from one worker's scrape: its
        snapshot epoch, and the delta of its request total since the last
        merge (credited to the shard-labeled counter)."""
        total = 0.0
        for fam in families:
            if fam.name == "router_snapshot_epoch":
                for s in fam.samples:
                    SHARD_SNAPSHOT_EPOCH.labels(str(shard)).set(s.value)
            elif fam.name == "inference_extension_request":
                total += sum(s.value for s in fam.samples
                             if s.name == "inference_extension_request_total")
        prev = self._credited.get(shard, 0.0)
        if total > prev:
            SHARD_REQUESTS.labels(str(shard)).inc(total - prev)
        self._credited[shard] = total

    async def health(self, request: web.Request) -> web.Response:
        results = await self._fan_out("/health")
        workers = []
        ready = 0
        all_accounted = True
        for shard, (status, doc) in enumerate(results):
            alive = status != 0 and self.worker_alive(shard)
            state = self.worker_state(shard)
            # A shard the actuator deliberately scaled in is ACCOUNTED
            # FOR, not broken: "retiring" (still draining its flows) and
            # "retired" (gone on purpose) must not flip fleet readiness
            # to 503 the way a crashed worker does — else every scale-in
            # looks like an outage to the probe watching /health.
            all_accounted = all_accounted and (
                alive or state in ("retiring", "retired"))
            if status == 200:
                ready += 1
            workers.append({"shard": shard, "alive": alive,
                            "state": state,
                            "status": (doc if isinstance(doc, dict)
                                       else None)})
        # A permanently-down shard must surface here, not hide behind the
        # healthy ones: in hash-balancer mode it blackholes its flows, and
        # a dead shard-0 leader freezes every follower's pool view. One
        # transiently-restarting worker flips readiness for a beat — the
        # probe-tolerant kind of honest.
        ok = ready > 0 and all_accounted
        return web.json_response(
            {"status": "ok" if ok else "not-ready",
             "workers_ready": ready, "workers": workers},
            status=200 if ok else 503)

    async def fleet_view(self, request: web.Request) -> web.Response:
        """The fleet role table: who leads the datalayer (divergence is
        measured against that shard), how many elections have run, and the
        per-worker liveness/restart tallies a kill-the-leader chaos run
        asserts against."""
        state = self.fleet_state()
        leader = int(state.get("leader", 0))
        restarts = state.get("restarts") or []
        return web.json_response({
            "workers": len(self.worker_admin),
            "leader": leader,
            "elections_total": int(state.get("elections", 0)),
            "admin": [{"shard": i, "host": h, "port": p,
                       "alive": self.worker_alive(i),
                       "state": self.worker_state(i),
                       "role": "leader" if i == leader else "follower",
                       "restarts": (restarts[i] if i < len(restarts)
                                    else 0)}
                      for i, (h, p) in enumerate(self.worker_admin)],
        })

    async def decisions(self, request: web.Request) -> web.Response:
        """One list across shards: each worker's recent records, annotated
        with the owning shard, newest first — trimmed to the page size the
        caller asked for (same contract as the single-process endpoint)."""
        try:
            n = max(1, int(request.query.get("n", "50")))
        except ValueError:
            n = 50
        # Operator filters (?verdict=/?endpoint=/?outcome=/?profile=)
        # forward to every worker so each shard filters ring-side; the
        # merge trims the union.
        from urllib.parse import urlencode

        params = {"n": str(n)}
        for key in ("verdict", "endpoint", "outcome", "profile",
                    "divergent", "stage"):
            v = request.query.get(key)
            if v:
                params[key] = v
        results = await self._fan_out(f"/debug/decisions?{urlencode(params)}")
        merged: list[dict] = []
        enabled = False
        count = 0
        schema = None
        for shard, (status, doc) in enumerate(results):
            if status != 200 or not isinstance(doc, dict):
                continue
            enabled = enabled or bool(doc.get("enabled"))
            count += doc.get("count", 0)
            schema = schema or doc.get("schema_version")
            for rec in doc.get("decisions") or []:
                rec["shard"] = shard
                merged.append(rec)
        merged.sort(key=lambda r: r.get("start_unix") or 0, reverse=True)
        return web.json_response({"schema_version": schema,
                                  "enabled": enabled, "count": count,
                                  "decisions": merged[:n]})

    async def decision_detail(self, request: web.Request) -> web.Response:
        """Route the lookup to the owning shard: the record lives in
        exactly one worker's ring (the one that served the request)."""
        rid = request.match_info["request_id"]
        results = await self._fan_out(f"/debug/decisions/{rid}")
        for shard, (status, doc) in enumerate(results):
            if status == 200 and isinstance(doc, dict):
                doc["shard"] = shard
                return web.json_response(doc)
        return web.json_response(
            {"error": f"no decision record for request id {rid!r} "
                      "in any shard"}, status=404)

    async def slo(self, request: web.Request) -> web.Response:
        results = await self._fan_out("/debug/slo")
        return web.json_response(merge_slo(
            [doc for status, doc in results
             if status == 200 and isinstance(doc, dict)]))

    async def kv(self, request: web.Request) -> web.Response:
        """Fleet /debug/kv: per-shard cache-ledger snapshots with the
        follower-vs-leader index divergence gauge (merge_kv), measured
        against the CURRENT datalayer leader (elections move it)."""
        results = await self._fan_out("/debug/kv")
        return web.json_response(merge_kv(
            [(shard, doc) for shard, (status, doc) in enumerate(results)
             if status == 200 and isinstance(doc, dict)],
            leader_shard=int(self.fleet_state().get("leader", 0))))

    async def transfers(self, request: web.Request) -> web.Response:
        """Fleet /debug/transfers: per-pair EWMAs merged n-weighted across
        shards (merge_transfers) — the same (prefill, decode) pair seen by
        multiple shards is ONE row, not duplicates."""
        results = await self._fan_out("/debug/transfers")
        return web.json_response(merge_transfers(
            [(shard, doc) for shard, (status, doc) in enumerate(results)
             if status == 200 and isinstance(doc, dict)]))

    async def tails(self, request: web.Request) -> web.Response:
        """Fleet /debug/tails: per-cohort stage digests merged n-weighted
        across shards (router/tails.py merge_tails) — exemplars carry the
        owning shard so a drill-down knows which worker's ring to ask."""
        from .tails import merge_tails

        results = await self._fan_out("/debug/tails")
        return web.json_response(merge_tails(
            [(shard, doc) for shard, (status, doc) in enumerate(results)
             if status == 200 and isinstance(doc, dict)]))

    async def shadow(self, request: web.Request) -> web.Response:
        """Fleet /debug/shadow: per-policy counterfactual rollups merged
        n-weighted across shards (router/shadow.py merge_shadow)."""
        from .shadow import merge_shadow

        results = await self._fan_out("/debug/shadow")
        return web.json_response(merge_shadow(
            [(shard, doc) for shard, (status, doc) in enumerate(results)
             if status == 200 and isinstance(doc, dict)]))

    async def rebalance(self, request: web.Request) -> web.Response:
        """Fleet /debug/rebalance: the datalayer-owning worker's controller
        doc (flips, headroom, advice) merged with every follower's compact
        row (router/rebalance.py merge_rebalance)."""
        from .rebalance import merge_rebalance

        results = await self._fan_out("/debug/rebalance")
        return web.json_response(merge_rebalance(
            [(shard, doc) for shard, (status, doc) in enumerate(results)
             if status == 200 and isinstance(doc, dict)]))

    async def forecast(self, request: web.Request) -> web.Response:
        """Fleet /debug/forecast: every worker's judged forecast ledger
        merged n-weighted per (series, horizon) — each shard forecasts
        its own traffic slice, so join counts are the vote weights and
        skill recomputes from the merged MAEs (router/forecast.py
        merge_forecast). The query string forwards verbatim (?joins=N)."""
        from .forecast import merge_forecast

        qs = request.query_string
        path = "/debug/forecast" + (f"?{qs}" if qs else "")
        results = await self._fan_out(path)
        return web.json_response(merge_forecast(
            [(shard, doc) for shard, (status, doc) in enumerate(results)
             if status == 200 and isinstance(doc, dict)]))

    async def autoscale(self, request: web.Request) -> web.Response:
        """Fleet /debug/autoscale: the acting shard's actuator ledger
        (actions, refusals, rollbacks, freeze state) beside every
        follower's dormant row, shard-tagged and merged newest-first
        (router/autoscale.py merge_autoscale) — plus the supervisor's
        own worker states so a scale-in reads end to end."""
        from .autoscale import merge_autoscale

        results = await self._fan_out("/debug/autoscale")
        merged = merge_autoscale(
            [(shard, doc) for shard, (status, doc) in enumerate(results)
             if status == 200 and isinstance(doc, dict)])
        merged["worker_states"] = [
            self.worker_state(i) for i in range(len(self.worker_admin))]
        return web.json_response(merged)

    async def scale(self, request: web.Request) -> web.Response:
        """Worker-dimension scale surface for the elastic-fleet actuator:
        ``{"action": "retire"|"restore", "shard": optional}``. Guarded by
        the per-run fleet control token (same spoofing argument as
        /fleet/promote); refusals (leader, last worker) come back 409
        with the reason so the actuator ledger can record it."""
        if self.scale_fn is None:
            return web.json_response(
                {"error": "no supervisor scale hooks"}, status=501)
        if (self.control_token
                and request.headers.get("x-fleet-token")
                != self.control_token):
            return web.json_response({"error": "bad token"}, status=403)
        try:
            body = await request.json()
        except Exception:
            body = {}
        action = (body or {}).get("action")
        if action not in ("retire", "restore"):
            return web.json_response(
                {"error": "action must be retire|restore"}, status=400)
        shard = (body or {}).get("shard")
        shard = int(shard) if shard is not None else None
        result = self.scale_fn(action, shard)
        if asyncio.iscoroutine(result):
            result = await result
        if result is None:
            return web.json_response(
                {"action": action, "refused": True}, status=409)
        return web.json_response({"action": action, "shard": result})

    async def traces(self, request: web.Request) -> web.Response:
        """Cross-shard trace fan-in: every worker's /debug/traces merged,
        deduped by span_id. The query string forwards verbatim, so
        ``?merge=1`` additionally pulls each worker's POOL endpoints
        (sidecars/engines) through the workers' own merge path — before
        this, traces stopped at the worker boundary while every other
        fan-in table re-served its surface."""
        qs = request.query_string
        path = "/debug/traces" + (f"?{qs}" if qs else "")
        results = await self._fan_out(path)
        seen: set[str] = set()
        spans: list[dict] = []
        for shard, (status, doc) in enumerate(results):
            if status != 200 or not isinstance(doc, dict):
                continue
            for s in doc.get("spans") or []:
                if isinstance(s, dict) and s.get("span_id") not in seen:
                    seen.add(s.get("span_id"))
                    s["shard"] = shard
                    spans.append(s)
        return web.json_response({"spans": spans})

    async def timeline(self, request: web.Request) -> web.Response:
        """Merged fleet timeline: per-worker rings bucketed by wall clock
        (gaps marked when a shard was down — no interpolation) beside the
        supervisor's divergence series (router/timeline.py
        merge_timeline)."""
        from .slo import finite_float_or_none
        from .timeline import merge_timeline

        qs = request.query_string
        path = "/debug/timeline" + (f"?{qs}" if qs else "")
        results = await self._fan_out(path)
        docs = [(shard, doc)
                for shard, (status, doc) in enumerate(results)
                if status == 200 and isinstance(doc, dict)]
        # The ?window_s trim the workers applied must also bound the
        # supervisor's divergence series, or a windowed query pays for —
        # and correlates against — supervisor samples whose wall-clock
        # range has no worker buckets at all.
        sup = list(self._sup_ring)
        window_s = finite_float_or_none(request.query.get("window_s"))
        if window_s and window_s > 0 and sup:
            cutoff = sup[-1]["t_unix"] - window_s
            sup = [s for s in sup if s["t_unix"] >= cutoff]
        return web.json_response(merge_timeline(
            docs, workers=len(self.worker_admin), supervisor=sup))

    async def incidents(self, request: web.Request) -> web.Response:
        """All incident snapshots: each worker's ring shard-annotated,
        plus the supervisor's own (divergence-rule) incidents, newest
        first."""
        results = await self._fan_out("/debug/incidents")
        merged: list[dict] = []
        for shard, (status, doc) in enumerate(results):
            if status != 200 or not isinstance(doc, dict):
                continue
            for inc in doc.get("incidents") or []:
                inc["shard"] = shard
                merged.append(inc)
        for inc in self._sup_incidents.snapshot()["incidents"]:
            inc = dict(inc)
            inc["shard"] = "supervisor"
            merged.append(inc)
        merged.sort(key=lambda i: i.get("first_unix") or 0, reverse=True)
        return web.json_response({"count": len(merged),
                                  "incidents": merged})

    async def config(self, request: web.Request) -> web.Response:
        """Fleet config-skew check: every worker's effective-config hash
        side by side (consistent = all responding shards agree), with the
        redacted snapshot served once from the lowest responding shard."""
        results = await self._fan_out("/debug/config")
        shards: list[dict] = []
        snapshot = None
        hashes: set[str] = set()
        for shard, (status, doc) in enumerate(results):
            if status != 200 or not isinstance(doc, dict):
                shards.append({"shard": shard, "hash": None})
                continue
            h = doc.get("hash")
            hashes.add(h)
            shards.append({"shard": shard, "hash": h})
            if snapshot is None:
                snapshot = doc.get("config")
        return web.json_response({
            "workers": len(self.worker_admin),
            # <= 1: zero responding shards is "no skew observed", not skew.
            "consistent": len(hashes) <= 1,
            "shards": shards,
            "config": snapshot,
        })


# ---------------------------------------------------------------------------
# Thin hash-by-flow-id front balancer (portable fallback to SO_REUSEPORT).
# ---------------------------------------------------------------------------

class HashBalancer:
    """Accepts on the public port and splices each connection to the worker
    owning its flow: the flow id is read from the FIRST request head on the
    connection (the flow-control fairness header, then the session token,
    then the request id, then the client address), hashed with
    ``flow_shard``. Keep-alive requests ride the same splice, so a client
    connection is sticky to its shard.

    The routing unit is the CONNECTION, deliberately — re-inspecting every
    request would make this a full HTTP proxy, not a thin splice. Flow →
    shard ownership therefore holds when a connection carries one flow
    (direct clients; proxies with per-flow/per-client upstream pools). A
    fronting proxy that multiplexes MANY flows over one pooled keep-alive
    connection gets connection-affinity only — the later flows land on the
    first flow's shard (correct service, diluted ownership; see
    docs/performance.md §Scale-out).

    The fallback order is a deliberate throughput/ownership dial: strict
    ownership applies to traffic that DECLARES a flow identity (the
    fairness header the flow-control plane keys on, or a session token).
    Anonymous traffic — no flow headers — deliberately SPREADS: a
    client-sent request id varies per request and the final fallback is
    the peer ADDRESS (no ephemeral port, so one client keeps shard
    affinity across reconnects). Pinning all headerless traffic to the
    gateway's single default flow would serialize the whole anonymous
    workload onto one worker and undo the scale-out for exactly the
    commonest client."""

    FLOW_HEADERS = ("x-gateway-inference-fairness-id", "x-session-token",
                    "x-request-id")
    HEAD_MAX = 64 << 10

    def __init__(self, host: str, port: int,
                 targets: list[tuple[str, int]]):
        self.host, self.port = host, port
        self.targets = targets
        self._server: asyncio.AbstractServer | None = None
        # Shards the supervisor pulled from rotation (retiring/retired):
        # NEW connections whose flow hashes there remap onto the alive
        # set (stable re-hash over the survivors), while splices already
        # established keep running — that is the drain. An empty set is
        # the PR 8 behavior bit-for-bit.
        self.disabled: set[int] = set()

    def disable(self, shard: int) -> None:
        self.disabled.add(shard)

    def enable(self, shard: int) -> None:
        self.disabled.discard(shard)

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port, limit=self.HEAD_MAX)

    def close_listener(self) -> None:
        """Stop ACCEPTING without tearing down established splices: the
        first phase of an ordered fleet drain — new connections are
        refused while in-flight streams keep flowing until the workers
        finish draining them."""
        if self._server is not None:
            self._server.close()

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None

    def _flow_id(self, head: bytes, peer: Any) -> str:
        headers: dict[str, str] = {}
        for line in head.split(b"\r\n")[1:]:
            # RFC 7230: field-name ":" OWS field-value — the space after
            # the colon is optional, so split on the bare colon.
            name, sep, value = line.partition(b":")
            if sep:
                headers[name.decode("latin1").lower().strip()] = (
                    value.decode("latin1").strip())
        for h in self.FLOW_HEADERS:
            if headers.get(h):
                return headers[h]
        # Address only, NOT the (host, port) tuple: the ephemeral port
        # changes per connection, which would randomize instead of giving
        # the client stable shard affinity across reconnects.
        if isinstance(peer, (tuple, list)) and peer:
            return str(peer[0])
        return str(peer)

    async def _handle(self, cr: asyncio.StreamReader,
                      cw: asyncio.StreamWriter) -> None:
        try:
            try:
                head = await asyncio.wait_for(cr.readuntil(b"\r\n\r\n"),
                                              timeout=10.0)
            except (asyncio.TimeoutError, asyncio.IncompleteReadError,
                    asyncio.LimitOverrunError):
                return
            fid = self._flow_id(head, cw.get_extra_info("peername"))
            shard = flow_shard(fid, len(self.targets))
            if shard in self.disabled:
                # Re-hash over the alive shards only: flows owned by a
                # retiring worker move to a stable survivor; everyone
                # else keeps their original shard.
                alive = [i for i in range(len(self.targets))
                         if i not in self.disabled]
                if not alive:
                    cw.write(b"HTTP/1.1 503 Service Unavailable\r\n"
                             b"content-length: 0\r\n"
                             b"connection: close\r\n\r\n")
                    with contextlib.suppress(Exception):
                        await cw.drain()
                    return
                shard = alive[flow_shard(fid, len(alive))]
            FLEET_BALANCER_CONNECTIONS.labels(str(shard)).inc()
            try:
                ur, uw = await asyncio.open_connection(*self.targets[shard])
            except OSError:
                cw.write(b"HTTP/1.1 503 Service Unavailable\r\n"
                         b"content-length: 0\r\nconnection: close\r\n\r\n")
                with contextlib.suppress(Exception):
                    await cw.drain()
                return
            uw.write(head)
            try:
                await uw.drain()
                await asyncio.gather(self._pipe(cr, uw),
                                     self._pipe(ur, cw))
            finally:
                with contextlib.suppress(Exception):
                    uw.close()
        finally:
            with contextlib.suppress(Exception):
                cw.close()

    @staticmethod
    async def _pipe(reader: asyncio.StreamReader,
                    writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                chunk = await reader.read(1 << 16)
                if not chunk:
                    break
                writer.write(chunk)
                await writer.drain()
        except (ConnectionError, OSError):
            pass
        finally:
            with contextlib.suppress(Exception):
                writer.write_eof()


# ---------------------------------------------------------------------------
# Supervisor: spawn + monitor the worker processes.
# ---------------------------------------------------------------------------

def _worker_main(spec: dict[str, Any]) -> None:
    """Worker-process entry (multiprocessing spawn target): one full
    gateway — own event loop, scheduler pool, flow-control shards — with
    the fleet identity steering listen-socket sharing and the datalayer
    leader/follower split."""
    logging.basicConfig(
        level=logging.INFO,
        format=f"%(asctime)s shard{spec['worker']['index']} "
               "%(name)s %(levelname)s %(message)s")
    from .gateway import build_gateway, run_gateway

    gw = build_gateway(spec["config_text"], host=spec["host"],
                       port=spec["port"],
                       poll_interval=spec["poll_interval"],
                       fleet=FleetWorkerSpec(**spec["worker"]))
    asyncio.run(run_gateway(gw, drain_timeout_s=spec["drain_timeout_s"]))


class FleetSupervisor:
    """Spawns N gateway workers, keeps them alive, and serves the fan-in
    admin plane. Worker 0 is the datalayer leader (scrape + SSE + snapshot
    publication); the rest are followers over the snapshot IPC stream."""

    def __init__(self, config_text: str | None, *, host: str = "127.0.0.1",
                 port: int = 8081, fleet: FleetConfig | None = None,
                 poll_interval: float = 0.05,
                 drain_timeout_s: float = 30.0):
        self.config_text = config_text
        self.host, self.port = host, port
        self.fleet = fleet or FleetConfig()
        self.poll_interval = poll_interval
        self.drain_timeout_s = drain_timeout_s
        if (self.fleet.balancer == "reuseport"
                and not hasattr(socket, "SO_REUSEPORT")):
            # The portable fallback the config names: platforms without
            # SO_REUSEPORT get the front balancer instead of a bind error.
            log.warning("SO_REUSEPORT unavailable on this platform; "
                        "falling back to fleet.balancer: hash")
            self.fleet = dataclasses.replace(self.fleet, balancer="hash")
        self.admin_port = self.fleet.admin_port or port + DEFAULT_ADMIN_OFFSET
        self.worker_admin = [("127.0.0.1", self.admin_port + 1 + i)
                             for i in range(self.fleet.workers)]
        # hash balancer: workers listen on private loopback ports behind
        # the public port; reuseport: all workers bind the public port.
        self._worker_ports = (
            [port] * self.fleet.workers if self.fleet.balancer == "reuseport"
            else [self.admin_port + 1 + self.fleet.workers + i
                  for i in range(self.fleet.workers)])
        self._ctx = multiprocessing.get_context("spawn")
        self._procs: list[Any] = [None] * self.fleet.workers
        self._restarts = [0] * self.fleet.workers
        self._ipc_dir: str | None = None
        self.ipc_path: str | None = None
        self.admin: FleetAdmin | None = None
        self.balancer: HashBalancer | None = None
        self._monitor: asyncio.Task | None = None
        self._stopping = False
        # Datalayer leadership (ISSUE 13b): worker 0 leads at boot; when
        # the leader process dies the monitor promotes the lowest-index
        # live follower onto a FRESH snapshot socket and re-targets the
        # rest. A restarted ex-leader rejoins as a follower (its respawn
        # spec is computed from leader_index at spawn time) — no
        # thrash-back.
        self.leader_index = 0
        self.elections_total = 0
        self._ipc_gen = 0
        self._election_session = None  # aiohttp session for promote/retarget
        # Followers whose retarget notice failed (e.g. caught mid-restart):
        # retried every monitor tick until acknowledged — a follower left
        # aimed at the dead leader's socket would otherwise retry it
        # forever.
        self._retarget_pending: set[int] = set()
        # An unacknowledged promotion (shard, path): a promote whose ack
        # was lost (timeout) may still have LANDED — the worker is a
        # de-facto leader. Until this resolves, the same (shard, path) is
        # re-sent each tick (promote is idempotent worker-side) and the
        # dead ex-leader is NOT respawned — respawning it as a leader
        # beside a half-promoted follower would split-brain the datalayer
        # with no reconciliation path.
        self._pending_promote: tuple[int, str] | None = None
        # Elastic-fleet scale-in bookkeeping (ISSUE 17): a shard the
        # actuator deliberately retires moves up -> retiring (SIGTERM
        # sent, worker draining its flows) -> retired (process exited on
        # purpose). The monitor must NOT respawn it, /health must not
        # read it as an outage, and restore_worker() re-spawns it on a
        # scale-up.
        self._retiring: set[int] = set()
        self._retired: set[int] = set()
        import secrets

        self._control_token = secrets.token_hex(16)

    def _worker_spec(self, i: int) -> dict[str, Any]:
        return {
            "config_text": self.config_text,
            "host": self.host if self.fleet.balancer == "reuseport"
            else "127.0.0.1",
            "port": self._worker_ports[i],
            "poll_interval": self.poll_interval,
            "drain_timeout_s": self.drain_timeout_s,
            "worker": {
                "index": i,
                "workers": self.fleet.workers,
                # Role follows CURRENT leadership, not the boot layout: a
                # worker respawned after a re-election must rejoin as a
                # follower of the promoted leader, not thrash leadership
                # back by scraping + publishing beside it.
                "role": "leader" if i == self.leader_index else "follower",
                "ipc_path": self.ipc_path,
                "admin_host": self.worker_admin[i][0],
                "admin_port": self.worker_admin[i][1],
                "reuse_port": self.fleet.balancer == "reuseport",
                "replication": self.fleet.replication,
                "kv_checkpoint_s": self.fleet.kv_checkpoint_s,
                "wire": self.fleet.wire,
                "control_token": self._control_token,
                "sup_admin_port": self.admin_port,
            },
        }

    def _spawn(self, i: int) -> None:
        proc = self._ctx.Process(target=_worker_main,
                                 args=(self._worker_spec(i),),
                                 name=f"router-shard-{i}", daemon=True)
        proc.start()
        self._procs[i] = proc
        log.info("spawned gateway shard %d/%d (pid %s, port %s, admin %s)",
                 i, self.fleet.workers, proc.pid, self._worker_ports[i],
                 self.worker_admin[i][1])

    def worker_alive(self, i: int) -> bool:
        p = self._procs[i]
        return p is not None and p.is_alive()

    def worker_state(self, i: int) -> str:
        """Lifecycle state for the admin plane: ``retiring`` (SIGTERM
        sent, still draining) and ``retired`` (deliberately gone) are
        distinct from ``down`` (crashed) — a scale-in is not an
        outage."""
        if i in self._retired:
            return "retired"
        if i in self._retiring:
            return "retiring" if self.worker_alive(i) else "retired"
        return "up" if self.worker_alive(i) else "down"

    def active_workers(self) -> int:
        """Workers still in rotation: alive and not being drained."""
        return sum(1 for i in range(self.fleet.workers)
                   if self.worker_alive(i) and i not in self._retiring
                   and i not in self._retired)

    def retire_worker(self, shard: int | None = None) -> int | None:
        """Scale one worker in: pull its NEW flows out of the balancer
        rotation, then SIGTERM it — run_gateway's drain path flips
        readiness, waits out in-flight requests (bounded by the drain
        timeout), and exits. Returns the shard, or None on refusal: the
        datalayer leader never retires (promote first), nor does the
        last active worker."""
        if shard is None:
            candidates = [i for i in range(self.fleet.workers - 1, -1, -1)
                          if self.worker_alive(i) and i != self.leader_index
                          and i not in self._retiring
                          and i not in self._retired]
            shard = candidates[0] if candidates else None
        if (shard is None or shard == self.leader_index
                or not self.worker_alive(shard)
                or shard in self._retiring or shard in self._retired
                or self.active_workers() <= 1):
            return None
        self._retiring.add(shard)
        if self.balancer is not None:
            self.balancer.disable(shard)
        self._procs[shard].terminate()  # SIGTERM -> worker-side drain
        log.info("retiring gateway shard %d (scale-in): flows re-hashed, "
                 "SIGTERM sent, drain bounded by %.0fs",
                 shard, self.drain_timeout_s)
        return shard

    def restore_worker(self, shard: int | None = None) -> int | None:
        """Scale a retired worker back out: respawn the process (its
        spec follows CURRENT leadership) and put its hash slice back in
        rotation. Returns the shard, or None when nothing is retired."""
        if shard is None:
            retired = sorted(self._retired
                             | {i for i in self._retiring
                                if not self.worker_alive(i)})
            shard = retired[0] if retired else None
        if shard is None or self.worker_alive(shard):
            return None
        if shard not in self._retired and shard not in self._retiring:
            return None
        self._retiring.discard(shard)
        self._retired.discard(shard)
        self._spawn(shard)
        if self.balancer is not None:
            self.balancer.enable(shard)
        log.info("restored gateway shard %d (scale-out)", shard)
        return shard

    def _scale_request(self, action: str, shard: int | None) -> int | None:
        """POST /fleet/scale dispatch (FleetAdmin scale_fn)."""
        if action == "retire":
            return self.retire_worker(shard)
        return self.restore_worker(shard)

    async def start(self) -> None:
        FLEET_WORKERS.set(self.fleet.workers)
        self._set_leader_gauge()
        if self.fleet.snapshot_ipc and self.fleet.workers > 1:
            self._ipc_dir = tempfile.mkdtemp(prefix="router-fleet-")
            self.ipc_path = os.path.join(self._ipc_dir, "snapshot.sock")
        try:
            for i in range(self.fleet.workers):
                self._spawn(i)
            await self._wait_ready()
            from .config.loader import load_raw_config
            from .timeline import TimelineConfig

            self.admin = FleetAdmin(
                self.worker_admin, host="127.0.0.1", port=self.admin_port,
                worker_alive=self.worker_alive,
                timeline=TimelineConfig.from_spec(
                    load_raw_config(self.config_text).timeline),
                fleet_state=lambda: {"leader": self.leader_index,
                                     "elections": self.elections_total,
                                     "restarts": list(self._restarts)},
                worker_state=self.worker_state,
                scale_fn=self._scale_request,
                control_token=self._control_token)
            await self.admin.start()
            if self.fleet.balancer == "hash":
                self.balancer = HashBalancer(
                    self.host, self.port,
                    [("127.0.0.1", p) for p in self._worker_ports])
                await self.balancer.start()
        except BaseException:
            # A failed startup must not strand worker processes (or the
            # IPC tempdir) behind the raised error.
            await self.stop()
            raise
        self._monitor = asyncio.get_running_loop().create_task(
            self._monitor_loop())
        log.info("fleet up: %d workers, balancer=%s, admin :%d%s",
                 self.fleet.workers, self.fleet.balancer, self.admin_port,
                 f", snapshot IPC {self.ipc_path}" if self.ipc_path else "")

    async def _wait_ready(self) -> None:
        """Block until every worker's admin listener answers (any status —
        a 503 not-ready still proves the process booted)."""
        import aiohttp

        deadline = time.monotonic() + WORKER_READY_TIMEOUT_S
        pending = set(range(self.fleet.workers))
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1.0)) as session:
            while pending and time.monotonic() < deadline:
                for i in list(pending):
                    host, port = self.worker_admin[i]
                    try:
                        async with session.get(
                                f"http://{host}:{port}/health"):
                            pass
                        pending.discard(i)
                    except Exception:
                        if not self.worker_alive(i):
                            raise RuntimeError(
                                f"fleet worker {i} died during startup "
                                f"(exitcode {self._procs[i].exitcode})")
                if pending:
                    await asyncio.sleep(0.1)
        if pending:
            raise RuntimeError(
                f"fleet workers {sorted(pending)} not ready after "
                f"{WORKER_READY_TIMEOUT_S:.0f}s")

    def _set_leader_gauge(self) -> None:
        for i in range(self.fleet.workers):
            FLEET_LEADER.labels(str(i)).set(
                1.0 if i == self.leader_index else 0.0)

    def _restart_allowed(self, i: int) -> bool:
        """The restart budget bounds follower crash loops; the CURRENT
        datalayer leader is exempt — a permanently dead leader freezes
        every follower's pool view, so it always respawns (the 1 s monitor
        tick is the backoff). The exemption follows LEADERSHIP, not the
        literal index 0: a promoted leader that crash-loops would
        otherwise be budget-killed and freeze the fleet exactly like the
        dead-worker-0 bug this PR fixes."""
        return i == self.leader_index or self._restarts[i] < MAX_WORKER_RESTARTS

    async def _elect_leader(self) -> None:
        """The dead datalayer leader's replacement: promote the
        lowest-index live follower onto a FRESH snapshot socket, then
        notify the remaining followers to re-target (event-driven — their
        subscribers would otherwise back off against a socket that will
        never answer again). On promotion failure the leader index is left
        unchanged and the next monitor tick retries."""
        if self._pending_promote is not None:
            # Resolve the in-flight promotion before anything else: the
            # lost ack may have been a completed promote (split-brain if
            # we elect elsewhere or respawn the old leader as leader).
            new_leader, new_path = self._pending_promote
            if not self.worker_alive(new_leader):
                # The half-promoted candidate died; its respawn spec is a
                # follower of whoever wins next, so the slate is clean.
                self._pending_promote = None
                return
        else:
            candidates = [i for i in range(self.fleet.workers)
                          if i != self.leader_index and self.worker_alive(i)]
            if not candidates:
                # Nobody to promote: the old leader respawns as leader on
                # the existing socket path (the pre-election behavior).
                return
            new_leader = min(candidates)
            self._ipc_gen += 1
            new_path = os.path.join(self._ipc_dir,
                                    f"snapshot-{self._ipc_gen}.sock")
            self._pending_promote = (new_leader, new_path)
        try:
            await self._fleet_control(new_leader, "promote", new_path)
        except Exception:
            log.exception("promoting shard %d to datalayer leader failed; "
                          "retrying the same promotion next tick",
                          new_leader)
            return
        self._pending_promote = None
        old = self.leader_index
        self.leader_index = new_leader
        self.ipc_path = new_path
        self.elections_total += 1
        LEADER_ELECTIONS.inc()
        self._set_leader_gauge()
        log.warning("datalayer leader re-elected: shard %d -> %d "
                    "(election %d, socket %s)", old, new_leader,
                    self.elections_total, new_path)
        self._retarget_pending = {i for i in range(self.fleet.workers)
                                  if i != new_leader}
        await self._drain_retargets()

    async def _fleet_control(self, shard: int, action: str,
                             path: str) -> None:
        import aiohttp

        if self._election_session is None:
            self._election_session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=5.0))
        host, port = self.worker_admin[shard]
        async with self._election_session.post(
                f"http://{host}:{port}/fleet/{action}",
                json={"ipcPath": path},
                headers={"x-fleet-token": self._control_token}) as resp:
            if resp.status != 200:
                raise RuntimeError(f"{action} returned {resp.status}")

    async def _drain_retargets(self) -> None:
        """Deliver the promotion notice to every pending follower. A
        failure (worker mid-restart, admin briefly down) keeps the shard
        pending and the next monitor tick retries — a follower must never
        be left aimed at the dead leader's socket indefinitely. Workers
        that are DEAD right now leave the set too: their respawn spec
        already carries the new path."""
        for i in sorted(self._retarget_pending):
            if i == self.leader_index:
                self._retarget_pending.discard(i)
                continue
            if not self.worker_alive(i):
                self._retarget_pending.discard(i)
                continue
            try:
                await self._fleet_control(i, "retarget", self.ipc_path)
                self._retarget_pending.discard(i)
            except Exception:
                log.warning("re-targeting shard %d to the new leader "
                            "socket failed; retrying next tick", i)

    async def _monitor_loop(self) -> None:
        try:
            while True:
                await asyncio.sleep(1.0)
                if self._stopping:
                    continue
                # Election BEFORE the respawn pass: the dead ex-leader must
                # respawn as a follower of the promoted leader (its spec is
                # computed from leader_index at spawn time).
                if (self.fleet.election and self.fleet.snapshot_ipc
                        and self.fleet.workers > 1 and self.ipc_path
                        and not self.worker_alive(self.leader_index)):
                    await self._elect_leader()
                if self._retarget_pending:
                    await self._drain_retargets()
                for i in range(self.fleet.workers):
                    # router_shard_up has ONE writer — the admin /metrics
                    # fan-in (scrape success implies process alive AND
                    # admin answering); this loop only restarts the dead.
                    alive = self.worker_alive(i)
                    if not alive and i in self._retiring:
                        # Deliberate exit, not a crash: the drain
                        # finished. Settle the state; never respawn.
                        self._retiring.discard(i)
                        self._retired.add(i)
                        log.info("gateway shard %d retired (drain "
                                 "complete)", i)
                        continue
                    if i in self._retired:
                        continue
                    if alive or self._stopping:
                        continue
                    if (i == self.leader_index
                            and self._pending_promote is not None):
                        # An unresolved promotion may already have a
                        # de-facto leader elsewhere: respawning the dead
                        # ex-leader AS a leader now would split-brain the
                        # datalayer. It respawns (as a follower) once the
                        # election resolves.
                        continue
                    if not self._restart_allowed(i):
                        continue
                    self._restarts[i] += 1
                    log.warning(
                        "gateway shard %d died (exitcode %s); restart %d%s",
                        i, self._procs[i].exitcode, self._restarts[i],
                        "" if i == self.leader_index
                        else f"/{MAX_WORKER_RESTARTS}")
                    self._spawn(i)
        except asyncio.CancelledError:
            pass

    async def stop(self) -> None:
        self._stopping = True
        if self._monitor is not None:
            self._monitor.cancel()
            self._monitor = None
        if self._election_session is not None:
            await self._election_session.close()
            self._election_session = None
        # Ordered drain (supervisor SIGTERM propagates as a graceful
        # scale-to-zero, not a guillotine): (1) stop ACCEPTING — the
        # balancer listener closes but established splices keep flowing;
        # (2) SIGTERM every worker — run_gateway flips readiness and
        # waits out its in-flight requests bounded by drain_timeout_s;
        # (3) join, escalating to SIGKILL only past the drain budget;
        # (4) only THEN tear down the balancer splices and admin plane.
        # Awaiting balancer.stop() before the workers exit would wait on
        # (or on older asyncio, silently abandon) splices that are still
        # carrying live streams — cutting them is exactly the mid-body
        # client error the drain exists to prevent.
        if self.balancer is not None:
            self.balancer.close_listener()
        for p in self._procs:
            if p is not None and p.is_alive():
                p.terminate()
        deadline = time.monotonic() + self.drain_timeout_s + 5.0
        for p in self._procs:
            if p is None:
                continue
            p.join(timeout=max(deadline - time.monotonic(), 0.1))
            if p.is_alive():
                p.kill()
                p.join(timeout=5.0)
        if self.balancer is not None:
            # Bounded: 3.12+ wait_closed() waits on every handler, and a
            # client that ignores the worker-side EOF could pin a splice
            # open forever.
            with contextlib.suppress(asyncio.TimeoutError):
                await asyncio.wait_for(self.balancer.stop(), timeout=5.0)
            self.balancer = None
        if self.admin is not None:
            await self.admin.stop()
            self.admin = None
        if self._ipc_dir is not None:
            shutil.rmtree(self._ipc_dir, ignore_errors=True)
            self._ipc_dir = None


async def _run_supervisor(sup: FleetSupervisor) -> None:
    await sup.start()
    stop_ev = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        with contextlib.suppress(NotImplementedError, RuntimeError):
            loop.add_signal_handler(sig, stop_ev.set)
    try:
        await stop_ev.wait()
    except asyncio.CancelledError:
        pass
    await sup.stop()


def run_fleet(config_text: str | None, *, host: str = "127.0.0.1",
              port: int = 8081, fleet: FleetConfig | None = None,
              poll_interval: float = 0.05,
              drain_timeout_s: float = 30.0) -> None:
    """Run a sharded gateway fleet until SIGTERM/SIGINT (the multi-process
    counterpart of gateway.run_gateway)."""
    sup = FleetSupervisor(config_text, host=host, port=port, fleet=fleet,
                          poll_interval=poll_interval,
                          drain_timeout_s=drain_timeout_s)
    asyncio.run(_run_supervisor(sup))


def main(argv: list[str] | None = None) -> None:
    import argparse

    p = argparse.ArgumentParser(
        description="TPU inference router gateway fleet (multi-process "
                    "sharded scale-out)")
    p.add_argument("--config-file", default=None)
    p.add_argument("--config-text", default=None)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8081)
    p.add_argument("--workers", type=int, default=None,
                   help="override fleet.workers from the config")
    p.add_argument("--balancer", choices=("reuseport", "hash"), default=None,
                   help="override fleet.balancer")
    p.add_argument("--admin-port", type=int, default=None,
                   help="supervisor fan-in admin port (default: port+1000)")
    p.add_argument("--no-snapshot-ipc", action="store_true",
                   help="every worker runs its own scrape pipeline instead "
                        "of replicating the leader's snapshots (N x scrape "
                        "load on every engine)")
    p.add_argument("--poll-interval", type=float, default=0.05)
    p.add_argument("--drain-timeout", type=float, default=30.0)
    args = p.parse_args(argv)

    text = args.config_text
    if args.config_file:
        with open(args.config_file) as f:
            text = f.read()

    from .config.loader import load_raw_config

    spec = dict(load_raw_config(text).fleet)
    if args.workers is not None:
        spec["workers"] = args.workers
    if args.balancer is not None:
        spec["balancer"] = args.balancer
    if args.admin_port is not None:
        spec["adminPort"] = args.admin_port
    if args.no_snapshot_ipc:
        spec["snapshotIpc"] = False
    fleet = FleetConfig.from_spec(spec)

    logging.basicConfig(level=logging.INFO)
    if fleet.workers <= 1:
        # workers: 1 IS the single-process router — no supervisor, no IPC,
        # bit-identical to the pre-fleet gateway. Build it directly (the
        # same build_gateway + run_gateway path gateway.main takes) rather
        # than delegating through gateway.main's argv: that both pins the
        # explicit `--workers 1` override against a config declaring
        # workers > 1, and honors --poll-interval, which gateway.main's
        # CLI does not expose.
        from .gateway import build_gateway, run_gateway

        gw = build_gateway(text, host=args.host, port=args.port,
                           poll_interval=args.poll_interval)
        asyncio.run(run_gateway(gw, drain_timeout_s=args.drain_timeout))
        return
    run_fleet(text, host=args.host, port=args.port, fleet=fleet,
              poll_interval=args.poll_interval,
              drain_timeout_s=args.drain_timeout)


if __name__ == "__main__":
    main()
