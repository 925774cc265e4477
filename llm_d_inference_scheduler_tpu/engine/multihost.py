"""Multi-host serving: leader fan-in over a jax.distributed global mesh.

A 70B-class deployment at real scale needs engines whose mesh spans hosts
(e.g. 70B TP-sharded over a v5e-16 multi-host slice). JAX is
multi-controller SPMD: EVERY process must enter the same jitted computation
in the same order. The reference has no analogue (its engines are external
vLLM processes); the shape here is JetStream-style:

- Process 0 (leader) runs the full engine: HTTP server, continuous-batching
  loop, allocator, prefix cache. Followers (process_id > 0) construct the
  same TpuEngine (joint sharded init — itself a collective) and then sit in
  :func:`run_follower`, replaying device ops.
- Every device call the engine makes is an *op*: a named method plus a dict
  of host numpy arrays (core.py `_OPS`). The leader broadcasts (op, args)
  over a TCP instruction channel before executing locally; followers decode
  and execute the same op. PRNG keys are never shipped: each process derives
  them from the same seeded stream, so replay order keeps them identical.
- Host inputs are device_put with a fully-replicated NamedSharding on the
  global mesh (every process feeds the same bytes), params/KV pages stay in
  their TP shards; XLA inserts the psums over ICI/DCN. Replication is a
  deliberate trade for serving: per-step host inputs are tiny ([B] token /
  position / sampling vectors, one [1, S] prefill row — kilobytes), so
  dp-sharding them via make_array_from_process_local_data would save
  nothing measurable while coupling the instruction protocol to the mesh
  layout. Weights and KV pages — the bytes that matter — are never
  replicated across the model axes.

Failure semantics (the part the reference gets from k8s restarting vLLM
pods): a process group is an SPMD unit — losing ANY member makes every
subsequent collective a deadlock, so recovery is always a coordinated
restart of the whole group, never an in-place rejoin.

- The leader watches each follower socket (after the one-time connect
  hello, followers never send, so a readable socket means EOF/death) and
  pings the group every
  ``PING_INTERVAL_S`` so followers can distinguish an idle leader from a
  dead one. Loss of a follower fires ``on_peer_lost``: the engine aborts
  all in-flight requests, refuses new ones, and reports degraded on
  /health (503) so the deployment restarts the pod set — instead of
  hanging inside the next collective.
- A follower whose ``recv`` hits EOF or the ping deadline raises
  :class:`LeaderLost`; ``run_follower`` re-raises so the process exits
  nonzero and the pod restarts.

The channel carries pickled tuples on a cluster-internal port — same trust
domain as the reference's engine-to-engine ZMQ/NIXL side channels.
"""

from __future__ import annotations

import logging
import pickle
import socket
import struct
import threading
import time
from typing import Any, Callable

log = logging.getLogger("engine.multihost")

_LEN = struct.Struct(">I")

PING_INTERVAL_S = 2.0
# Generous: a follower is only *in* recv between ops, and CI boxes pause
# for compiles; the ping thread keeps sending through leader-side compiles.
RECV_TIMEOUT_S = 30.0


class ChannelBroken(Exception):
    """Leader-side: one or more followers are gone; lockstep is over."""


class LeaderLost(Exception):
    """Follower-side: the leader is gone (EOF) or silent past the ping
    deadline."""


def maybe_init_distributed(cfg) -> bool:
    """jax.distributed.initialize from EngineConfig dist_* fields. Must run
    before first device use. Returns True when distributed mode is on."""
    if not cfg.dist_coordinator:
        return False
    import jax

    if cfg.dist_num_processes < 2:
        raise ValueError("dist_coordinator set but dist_num_processes < 2")
    jax.distributed.initialize(cfg.dist_coordinator,
                               num_processes=cfg.dist_num_processes,
                               process_id=cfg.dist_process_id)
    return True


class InstructionChannel:
    """Length-prefixed pickle fan-out: leader → all followers, with
    liveness both ways (peer monitors + pings, see module docstring)."""

    def __init__(self, *, leader: bool, host: str, port: int,
                 n_followers: int = 0, connect_timeout: float = 60.0,
                 ping_interval: float = PING_INTERVAL_S,
                 recv_timeout: float = RECV_TIMEOUT_S,
                 hello: dict[str, Any] | None = None):
        self.leader = leader
        self._lock = threading.Lock()
        self._state_lock = threading.Lock()
        self._closed = False
        self._lost: set[int] = set()
        self.on_peer_lost: Callable[[int, str], None] | None = None
        # One-time follower→leader handshake: each follower announces itself
        # (process_id, KV transfer address) right after connecting — the only
        # bytes a follower ever sends. Keyed by process_id so sharded KV
        # exports can address per-process transfer servers
        # (core.py stage_kv op).
        self.hellos: dict[int, dict[str, Any]] = {}
        if leader:
            self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._srv.bind((host, port))
            self._srv.listen(n_followers)
            self._peers: list[socket.socket] = []
            deadline = time.monotonic() + connect_timeout
            self._srv.settimeout(connect_timeout)
            while len(self._peers) < n_followers:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"only {len(self._peers)}/{n_followers} followers "
                        "connected to the instruction channel")
                conn, addr = self._srv.accept()
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                log.info("follower connected from %s", addr)
                conn.settimeout(connect_timeout)
                try:
                    info = self._recv_one(conn)
                except (OSError, ConnectionError) as e:
                    conn.close()
                    raise ConnectionError(
                        f"follower at {addr} sent no hello: {e}") from e
                conn.settimeout(None)
                pid = int(info.get("process_id", len(self._peers) + 1))
                self.hellos[pid] = info
                self._peers.append(conn)
            self._threads = [
                threading.Thread(target=self._watch_peer, args=(i,),
                                 name=f"mh-watch-{i}", daemon=True)
                for i in range(n_followers)]
            if ping_interval > 0:
                self._threads.append(threading.Thread(
                    target=self._ping_loop, args=(ping_interval,),
                    name="mh-ping", daemon=True))
            for t in self._threads:
                t.start()
        else:
            deadline = time.monotonic() + connect_timeout
            last_err: Exception | None = None
            while True:
                try:
                    self._sock = socket.create_connection((host, port),
                                                          timeout=5.0)
                    break
                except OSError as e:
                    last_err = e
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"could not reach instruction channel: {e}") from e
                    time.sleep(0.2)
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._sock.settimeout(recv_timeout)
            payload = pickle.dumps(dict(hello or {}),
                                   protocol=pickle.HIGHEST_PROTOCOL)
            self._sock.sendall(_LEN.pack(len(payload)) + payload)

    @staticmethod
    def _recv_one(sock: socket.socket) -> dict[str, Any]:
        """Read one length-prefixed pickled message from ``sock``."""
        buf = b""
        while len(buf) < _LEN.size:
            chunk = sock.recv(_LEN.size - len(buf))
            if not chunk:
                raise ConnectionError("closed during hello")
            buf += chunk
        (ln,) = _LEN.unpack(buf)
        data = b""
        while len(data) < ln:
            chunk = sock.recv(ln - len(data))
            if not chunk:
                raise ConnectionError("closed during hello")
            data += chunk
        return pickle.loads(data)

    # ---- leader side ----------------------------------------------------

    def _peer_lost(self, idx: int, why: str) -> None:
        with self._state_lock:
            if self._closed or idx in self._lost:
                return
            self._lost.add(idx)
        log.error("follower %d lost (%s) — lockstep broken", idx, why)
        cb = self.on_peer_lost
        if cb is not None:
            try:
                cb(idx, why)
            except Exception:
                log.exception("on_peer_lost callback failed")

    def _watch_peer(self, idx: int) -> None:
        """Followers never send: a readable socket means EOF (death)."""
        sock = self._peers[idx]
        try:
            data = sock.recv(1)
        except OSError as e:
            if not self._closed:
                self._peer_lost(idx, f"socket error: {e}")
            return
        if not self._closed:
            self._peer_lost(idx, "EOF" if not data else "unexpected data")

    def _ping_loop(self, interval: float) -> None:
        while not self._closed:
            time.sleep(interval)
            if self._closed:
                return
            try:
                self.broadcast(("ping",), {})
            except ChannelBroken:
                pass  # on_peer_lost already fired; keep pinging survivors

    def broadcast(self, op: tuple, args: dict[str, Any]) -> None:
        if op[0] == "stop":
            # Mark closed BEFORE the bytes leave: a follower may exit (EOF
            # on its socket) the instant it decodes stop, and _watch_peer
            # must not report that normal exit as a lost peer.
            with self._state_lock:
                self._closed = True
        payload = pickle.dumps((op, args), protocol=pickle.HIGHEST_PROTOCOL)
        msg = _LEN.pack(len(payload)) + payload
        broken: list[int] = []
        with self._lock:
            for i, peer in enumerate(self._peers):
                if i in self._lost:
                    continue
                try:
                    peer.sendall(msg)
                except OSError:
                    broken.append(i)
        for i in broken:
            self._peer_lost(i, "send failed")
        if self._lost and not self._closed:
            raise ChannelBroken(f"followers lost: {sorted(self._lost)}")

    # ---- follower side --------------------------------------------------

    def recv(self) -> tuple[tuple, dict[str, Any]]:
        try:
            hdr = self._recv_exact(_LEN.size)
            (ln,) = _LEN.unpack(hdr)
            return pickle.loads(self._recv_exact(ln))
        except socket.timeout as e:
            raise LeaderLost(
                f"no instruction or ping within {self._sock.gettimeout()}s "
                "— leader presumed dead/hung") from e
        except ConnectionError as e:
            raise LeaderLost(f"instruction channel closed: {e}") from e

    def _recv_exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self._sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("instruction channel closed")
            buf += chunk
        return buf

    def close(self) -> None:
        self._closed = True
        if self.leader:
            for peer in self._peers:
                peer.close()
            self._srv.close()
        else:
            self._sock.close()


def run_follower(engine) -> None:
    """Replay loop for process_id > 0: executes the leader's device ops in
    order until the ("stop",) instruction arrives. Raises LeaderLost when
    the leader dies or goes silent — exit nonzero so the deployment
    restarts the whole SPMD group (in-place rejoin is impossible: the
    group's collectives require every member)."""
    chan = engine._instr_channel
    mesh = engine.mesh or engine.pp_mesh
    log.info("follower %d ready (mesh %s)", engine.cfg.dist_process_id,
             mesh.shape if mesh is not None else None)
    while True:
        try:
            op, args = chan.recv()
        except LeaderLost:
            log.exception("leader lost; follower exiting for restart")
            raise
        if op[0] == "ping":
            continue
        if op[0] == "stop":
            log.info("follower stopping")
            return
        try:
            engine._exec_op(op, args)
        except Exception:
            # A follower that falls out of lockstep cannot recover (every
            # subsequent collective would deadlock) — crash loudly so the
            # deployment restarts the pod set.
            log.exception("follower op %s failed; aborting", op)
            raise
