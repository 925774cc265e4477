"""Off-loop scheduling: worker threads run ``Scheduler.schedule`` cycles so
routing-decision CPU stops head-of-line-blocking token streaming.

Every scheduling cycle used to execute synchronously on the gateway's
single asyncio event loop, interleaved with every live SSE token relay:
one 2 ms cycle (128-endpoint pool, benchmarks/SCHED_HOTPATH.json) stalled
every in-flight stream by 2 ms, and concurrent arrivals serialized.
``SchedulerPool`` moves the cycle into a small thread pool over the
copy-on-write pool snapshot (router/snapshot.py):

- config ``scheduling: {workers, maxBatch}``; ``workers: 0`` (the default)
  is the kill-switch — today's inline path, bit-identical behavior;
- the cycle's shared state is thread-safe by audit, not assumption:
  xxhash memoization (router/hashmemo.py) and the batched
  ``KvBlockIndex.match_prefix`` walk hold their own locks, and every
  in-tree filter/scorer/picker declares ``THREAD_SAFE`` (audited —
  ``scripts/verify_threadsafe.py`` lints the registry). Plugins that do
  NOT declare ``THREAD_SAFE = True`` are transparently trampolined back
  onto the event loop (correct, just not off-loop) so third-party plugins
  can't corrupt state;
- workers keep the GIL while scoring (Python threads don't parallelize
  the arithmetic — offload buys loop *responsiveness*, not cycle
  throughput), so the pool drops the interpreter switch interval to 1 ms
  once: a CPU-bound worker then yields the GIL to the loop within ~1 ms
  instead of the 5 ms default, bounding the residual stall.

``bench.py --sched-offload`` measures the event-loop stall (p50/p99
heartbeat lag) and streamed-token inter-arrival gap with offload on vs
off → benchmarks/SCHED_OFFLOAD.json.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
import logging
import sys
import threading
import time
from typing import Any

from .metrics import SCHED_OFFLOAD_QUEUE_SECONDS
from .scheduling.scheduler import Scheduler, SchedulerProfile, WeightedScorer

log = logging.getLogger("router.schedpool")

# GIL switch interval while scheduler workers churn: a worker holding the
# GIL for the default 5 ms would re-introduce most of the stall the offload
# removes. 1 ms bounds the loop's wait without measurable throughput cost
# at router scale (the cycles are ~2 ms total CPU).
WORKER_SWITCH_INTERVAL_S = 0.001

# The switch interval is PROCESS-global, so pools must refcount it: with two
# offloaded pools alive (an in-process multi-gateway test, a prefill+decode
# router pair), the first shutdown() must not revert the second pool's 1 ms
# responsiveness bound back to the 5 ms default.
_switch_lock = threading.Lock()
_switch_holders = 0
_switch_prev: float | None = None


def _switch_interval_acquire() -> None:
    global _switch_holders, _switch_prev
    with _switch_lock:
        _switch_holders += 1
        if _switch_holders == 1 and sys.getswitchinterval() > WORKER_SWITCH_INTERVAL_S:
            # Never raise an operator's already-lower setting.
            _switch_prev = sys.getswitchinterval()
            sys.setswitchinterval(WORKER_SWITCH_INTERVAL_S)


def _switch_interval_release() -> None:
    global _switch_holders, _switch_prev
    with _switch_lock:
        _switch_holders -= 1
        if _switch_holders == 0 and _switch_prev is not None:
            # Restore the interval we lowered (but leave it alone if someone
            # else changed it since).
            if sys.getswitchinterval() == WORKER_SWITCH_INTERVAL_S:
                sys.setswitchinterval(_switch_prev)
            _switch_prev = None


@dataclasses.dataclass
class SchedulingConfig:
    """The YAML ``scheduling:`` section. ``workers: 0`` = inline (today's
    path); ``maxBatch`` bounds how many flow-control items one shard wake
    dispatches into the pool (they share one snapshot epoch)."""

    workers: int = 0
    max_batch: int = 8
    # Columnar scheduling (router/snapshot.py PoolColumns): when True the
    # director hands the scheduler an EndpointBatch and plugins with batch
    # kernels run vectorized; scalar-only plugins fall back transparently
    # through the scheduler's auto-adapter. `vectorized: false` is the
    # kill-switch back to the pure scalar cycle.
    vectorized: bool = True

    @classmethod
    def from_spec(cls, spec: dict[str, Any] | None) -> "SchedulingConfig":
        spec = spec or {}
        return cls(workers=max(0, int(spec.get("workers", 0))),
                   max_batch=max(1, int(spec.get("maxBatch", 8))),
                   vectorized=bool(spec.get("vectorized", True)))


def _is_threadsafe(plugin: Any) -> bool:
    return getattr(plugin, "THREAD_SAFE", False) is True


def _handler_threadsafe(handler: Any) -> bool:
    """A profile handler is only as safe as the PD/encode deciders it
    delegates to: ``disaggregate()`` runs INSIDE ``pick_profiles`` (not at
    a call site the pool can wrap individually), so a decider declaring
    ``THREAD_SAFE = False`` drags the whole handler back onto the loop."""
    if not _is_threadsafe(handler):
        return False
    try:
        members = list(vars(handler).values())
    except TypeError:  # __slots__ handler: no instance dict to scan
        members = []
    return all(_is_threadsafe(d) for d in members
               if d is not None and hasattr(d, "disaggregate"))


class _LoopTrampoline:
    """Wraps a plugin that did not declare ``THREAD_SAFE = True``: calls
    from scheduler worker threads hop back onto the event loop (the
    plugin's single-writer world is preserved; the worker blocks on the
    result). On-loop calls — inline cycles, or the loop not running (unit
    tests driving the scheduler directly) — go straight through."""

    __slots__ = ("_plugin", "_loop")

    def __init__(self, plugin: Any, loop: asyncio.AbstractEventLoop):
        self._plugin = plugin
        self._loop = loop

    def typed_name(self):
        return self._plugin.typed_name()

    @property
    def wrapped(self) -> Any:
        return self._plugin

    def _call(self, fn, *args):
        loop = self._loop
        if loop is None or not loop.is_running():
            return fn(*args)
        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if running is loop:
            return fn(*args)
        cf: concurrent.futures.Future = concurrent.futures.Future()

        def run():
            try:
                cf.set_result(fn(*args))
            except BaseException as e:  # relayed to the waiting worker
                cf.set_exception(e)

        loop.call_soon_threadsafe(run)
        # Poll instead of blocking forever: if the loop stops before our
        # callback drains (gateway shutdown mid-cycle), the result never
        # arrives and an unbounded wait would wedge the worker thread —
        # and, through concurrent.futures' atexit join, the whole process.
        while True:
            try:
                return cf.result(timeout=1.0)
            except concurrent.futures.TimeoutError:
                if not loop.is_running():
                    raise RuntimeError(
                        "event loop stopped while waiting for trampolined "
                        f"plugin call to {self._plugin!r}") from None


class _FilterTrampoline(_LoopTrampoline):
    def filter(self, ctx, state, request, endpoints):
        return self._call(self._plugin.filter, ctx, state, request, endpoints)


class _ScorerTrampoline(_LoopTrampoline):
    def score(self, ctx, state, request, endpoints):
        return self._call(self._plugin.score, ctx, state, request, endpoints)


class _PickerTrampoline(_LoopTrampoline):
    def pick(self, ctx, state, request, scored):
        return self._call(self._plugin.pick, ctx, state, request, scored)


class _HandlerTrampoline(_LoopTrampoline):
    """Profile handlers run inside Scheduler.schedule too: pick_profiles /
    process_results execute off-loop every cycle (pre_request stays on the
    loop — the director calls it directly on the unwrapped plugin list)."""

    def pick_profiles(self, ctx, request, profiles, results):
        return self._call(self._plugin.pick_profiles, ctx, request,
                          profiles, results)

    def process_results(self, ctx, request, results):
        return self._call(self._plugin.process_results, ctx, request, results)


def trampoline_scheduler(scheduler: Scheduler,
                         loop: asyncio.AbstractEventLoop) -> Scheduler:
    """Clone the scheduler's profiles with every non-THREAD_SAFE
    filter/scorer/picker wrapped in a loop trampoline. Returns the original
    scheduler when nothing needed wrapping (the common all-in-tree case)."""
    profiles: dict[str, SchedulerProfile] = {}
    wrapped_any = False
    for name, prof in scheduler.profiles.items():
        fs = [f if _is_threadsafe(f) else _FilterTrampoline(f, loop)
              for f in prof.filters]
        ss = [ws if _is_threadsafe(ws.scorer)
              else WeightedScorer(_ScorerTrampoline(ws.scorer, loop), ws.weight)
              for ws in prof.scorers]
        pk = (prof.picker if _is_threadsafe(prof.picker)
              else _PickerTrampoline(prof.picker, loop))
        changed = (any(f is not o for f, o in zip(fs, prof.filters))
                   or any(s is not o for s, o in zip(ss, prof.scorers))
                   or pk is not prof.picker)
        if changed:
            wrapped_any = True
            wrapped = [w.typed_name() for w in
                       [f for f in fs if isinstance(f, _LoopTrampoline)]
                       + [s.scorer for s in ss
                          if isinstance(s.scorer, _LoopTrampoline)]
                       + ([pk] if isinstance(pk, _LoopTrampoline) else [])]
            log.info("profile %s: trampolining %s back onto the loop "
                     "(no THREAD_SAFE declaration)", name,
                     [str(w) for w in wrapped])
            profiles[name] = SchedulerProfile(prof.name, fs, ss, pk)
        else:
            profiles[name] = prof
    handler = scheduler.profile_handler
    if not _handler_threadsafe(handler):
        log.info("profile handler %s: trampolining pick_profiles/"
                 "process_results back onto the loop (handler or one of "
                 "its deciders lacks THREAD_SAFE = True)",
                 handler.typed_name())
        handler = _HandlerTrampoline(handler, loop)
        wrapped_any = True
    if not wrapped_any:
        return scheduler
    return Scheduler(profiles, handler)


class SchedulerPool:
    """Runs scheduling cycles inline (``workers: 0``) or on worker threads
    over snapshot views. One pool per gateway; its executor doubles as the
    CPU-offload pool for scrape-text parsing and large-body request
    parsing (the satellite offloads share the same threads — all three are
    pure-Python parse/score CPU that otherwise rides the event loop)."""

    def __init__(self, scheduler: Scheduler, cfg: SchedulingConfig | None = None):
        self.scheduler = scheduler
        self.cfg = cfg or SchedulingConfig()
        self._executor: concurrent.futures.ThreadPoolExecutor | None = None
        self._holds_switch_interval = False
        if self.cfg.workers > 0:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.cfg.workers, thread_name_prefix="sched-worker")
            # Bound the loop's GIL wait behind CPU-bound workers (see module
            # docstring). Refcounted: the interval is process-global.
            _switch_interval_acquire()
            self._holds_switch_interval = True
        self._loop: asyncio.AbstractEventLoop | None = None
        self._offload_scheduler: Scheduler | None = None

    @property
    def offloaded(self) -> bool:
        return self._executor is not None

    @property
    def vectorized(self) -> bool:
        return self.cfg.vectorized

    @property
    def executor(self) -> concurrent.futures.ThreadPoolExecutor | None:
        """Shared CPU-offload executor (None when ``workers: 0``)."""
        return self._executor

    def _bind(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop
        self._offload_scheduler = trampoline_scheduler(self.scheduler, loop)

    async def schedule(self, ctx: Any, request: Any,
                       candidates: list) -> Any:
        if self._executor is None:
            return self.scheduler.schedule(ctx, request, candidates)
        loop = asyncio.get_running_loop()
        if self._loop is not loop or self._offload_scheduler is None:
            self._bind(loop)
        sched = self._offload_scheduler
        t_submit = time.monotonic()

        def cycle():
            SCHED_OFFLOAD_QUEUE_SECONDS.observe(time.monotonic() - t_submit)
            return sched.schedule(ctx, request, candidates)

        return await loop.run_in_executor(self._executor, cycle)

    def shutdown(self) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=False, cancel_futures=True)
        if self._holds_switch_interval:
            self._holds_switch_interval = False
            _switch_interval_release()


class LoopLagMonitor:
    """Event-loop stall heartbeat: sleeps ``interval_s`` on the loop it is
    started on and records the overshoot into ``histogram`` — the gateway's
    ``router_loop_lag_seconds``, the engine server's
    ``jetstream:event_loop_lag_seconds``. The production twin of the bench's
    stall probe — the number the offload exists to shrink, live on /metrics
    so a regression (a new on-loop CPU hog) is graphable."""

    def __init__(self, histogram, interval_s: float = 0.1):
        self.histogram = histogram
        self.interval_s = interval_s
        self._task: asyncio.Task | None = None

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._run())

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None

    async def _run(self):
        loop = asyncio.get_running_loop()
        interval = self.interval_s
        try:
            while True:
                t0 = loop.time()
                await asyncio.sleep(interval)
                self.histogram.observe(max(loop.time() - t0 - interval, 0.0))
        except asyncio.CancelledError:
            pass
