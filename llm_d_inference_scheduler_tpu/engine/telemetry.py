"""Engine telemetry: the five-signal scrape contract + serving metrics.

The reference's entire engine-telemetry contract is five vllm:* metric names
scraped from each pod (/root/reference pkg/epp/server/options.go:121-125,
SURVEY §2.5). The TPU engines publish the same shapes under jetstream:* names;
the router's default extractor maps them (and can map vllm:* for heterogeneous
fleets via its mapping registry).
"""

from __future__ import annotations

import collections
import functools
import statistics
import threading
import time
from typing import Any

from prometheus_client import CollectorRegistry, Counter, Gauge, Histogram, generate_latest
from prometheus_client.core import CounterMetricFamily

from ..router.metrics import LOOP_LAG_BUCKETS, PERIOD_BUCKETS

WAITING = "jetstream:num_requests_waiting"
RUNNING = "jetstream:num_requests_running"
KV_USAGE = "jetstream:kv_cache_usage_perc"
KV_WINDOW_USAGE = "jetstream:kv_window_cache_usage_perc"
LORA_INFO = "jetstream:lora_requests_info"
CACHE_CONFIG = "jetstream:cache_config_info"

# The engine loop's phases (engine/core.py `_phase`). Each is a host span
# `engine.<phase>` in a profiler trace and a label of
# `jetstream:engine_loop_seconds_total`; they never nest. `decode_wait` is the
# loop blocked on the device: on the tokens of the chunk before the one in
# flight, or on a prefill's first token (with a chunk queued behind it).
LOOP_PHASES = ("housekeeping", "admit", "advance_prefills", "decode_prepare",
               "decode_dispatch", "decode_wait", "decode_book",
               "finalize_prefills", "idle_wait")

# Fine below a second: a wait is a fraction of one decode chunk (0.2-0.3 s
# at the benchmark's sizes), and a quantile read off coarser buckets would
# say nothing. Sum and count are exact whatever the buckets.
_WAIT_BUCKETS = (.001, .0025, .005, .01, .02, .035, .05, .075, .1, .15, .2,
                 .25, .3, .4, .5, .65, .8, 1, 1.5, 2.5, 5, 10, 30)

# A period of the engine loop (one decode chunk, readback to readback) is a
# stall when it is longer than STALL_RATIO times the median of the
# STALL_WINDOW periods before it AND longer than that median by
# STALL_MIN_EXCESS_S. The worst honest steps measured on the chip stay under
# one of the two: a 133 ms chunk behind four prefill windows of up to 62 ms
# is under twice its median; a 101 ms chunk at 7 lanes behind two 1,500-token
# prefills is 354 ms, 3.5 times its median but 253 ms over it (PERF.md
# section 6, PR 36). The stops this is for last 1.3 s and more. Nothing is
# judged before STALL_MIN_PERIODS periods are known.
STALL_WINDOW = 32
STALL_RATIO = 3.0
STALL_MIN_EXCESS_S = 0.5
STALL_MIN_PERIODS = 8
STALL_RING = 64
STALL_WHERE = ("device_wait", "host")

# The counters a model family's programs are booked in (book_program), by
# their attribute of EngineTelemetry: a new family declares its own below
# and names them here and in models/binding.py Bound.program_counts.
PROGRAM_COUNTERS = ("moe_ffn_tokens", "mla_attention_tokens",
                    "mla_window_attention_tokens",
                    "dsa_query_tokens", "dsa_rows", "ssm_tokens",
                    "ssm_state_updates", "ssm_slot_prefills", "swa_rows",
                    "kv_prefill_attention_tokens", "ssm_scan_tokens")


class XlaBuilds:
    """Programs JAX built in this process, from `jax.monitoring`'s events.

    JAX 0.9 records `/jax/core/compile/backend_compile_duration` once for
    every program it builds, whether the backend compiled it or the
    persistent cache held it; in the second case
    `/jax/compilation_cache/cache_hits` fires first, on the same thread.
    The events are the process's and a listener stays for its life, so
    there is one tally a process (`XLA_BUILDS`), started by `watch()` and
    shown by the registry of every engine that asked for it."""

    _BUILD = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self._lock = threading.Lock()
        self._watching = False
        self._hit = threading.local()
        self.counts = {"compiled": 0, "cache_loaded": 0}
        self.seconds = 0.0

    def watch(self) -> "XlaBuilds":
        with self._lock:
            first, self._watching = not self._watching, True
        if first:
            from jax import monitoring

            monitoring.register_event_listener(self._on_event)
            monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self._hit.pending = True

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event != self._BUILD:
            return
        loaded = getattr(self._hit, "pending", False)
        self._hit.pending = False
        with self._lock:
            self.counts["cache_loaded" if loaded else "compiled"] += 1
            self.seconds += duration

    def collect(self):
        builds = CounterMetricFamily(
            "jetstream:xla_builds",
            "Programs JAX built in this process: compiled by the backend, "
            "or loaded from the persistent compilation cache", labels=("kind",))
        with self._lock:
            for kind, n in self.counts.items():
                builds.add_metric((kind,), n)
            seconds = self.seconds
        yield builds
        yield CounterMetricFamily(
            "jetstream:xla_build_seconds",
            "Seconds spent building those programs (compile or cache load)",
            value=seconds)


XLA_BUILDS = XlaBuilds()


class EngineTelemetry:
    def __init__(self, *, block_size: int, num_blocks: int):
        self.registry = CollectorRegistry()
        g = lambda name, doc, labels=(): Gauge(name, doc, labels, registry=self.registry)
        self.waiting = g(WAITING, "Requests waiting for admission")
        self.running = g(RUNNING, "Requests actively decoding")
        self.kv_usage = g(KV_USAGE, "Fraction of HBM KV blocks in use")
        self.kv_window_usage = g(
            KV_WINDOW_USAGE,
            "Fraction of the window layers' page pool (kvcache/pages.py: the "
            "pool of the cache layers that keep a window of the context) held "
            "by live requests; 0 for a model without such layers")
        self.lora_info = g(LORA_INFO, "Active/waiting LoRA adapters",
                           ("running_lora_adapters", "waiting_lora_adapters", "max_lora"))
        self.cache_config = g(CACHE_CONFIG, "KV cache geometry",
                              ("block_size", "num_gpu_blocks"))
        # num_gpu_blocks: label name kept scrape-compatible with the reference's
        # extractor expectations; counts TPU HBM blocks.
        self.cache_config.labels(block_size=str(block_size), num_gpu_blocks=str(num_blocks)).set(1)
        self.lora_info.labels(running_lora_adapters="", waiting_lora_adapters="", max_lora="0").set(1)

        # Step-level instrumentation beyond the five-signal contract: block
        # occupancy, batch fill, per-dispatch step timing, and compile events
        # — the engine half of the cross-component latency attribution story
        # (router scrapes these via the jetstream mapping; docs/observability.md).
        self.free_blocks = g("jetstream:num_free_kv_blocks",
                             "KV blocks immediately allocatable (free list)")
        self.cached_blocks = g("jetstream:num_cached_kv_blocks",
                               "Parked reusable prefix-cache KV blocks")
        self.batch_fill = g("jetstream:batch_fill_ratio",
                            "Active decode lanes / max_batch last step")
        self.prefill_step = Histogram(
            "jetstream:prefill_step_duration_seconds",
            "Wall time of one prefill dispatch (post-compile)",
            registry=self.registry,
            buckets=(.001, .0025, .005, .01, .025, .05, .1, .25, .5, 1, 2.5))
        self.decode_step = Histogram(
            "jetstream:decode_step_duration_seconds",
            "Wall time of one fused decode chunk: from its dispatch, or from "
            "the readback of the chunk before it where that came later (the "
            "chunk was queued behind it), through its own readback",
            registry=self.registry, buckets=PERIOD_BUCKETS)
        self.compile_events = Counter(
            "jetstream:compile_events_total",
            "First dispatch of a novel (op, shape-bucket) key of the engine "
            "(not a count of compiles: jetstream:xla_builds_total is)",
            ("op", "bucket"), registry=self.registry)
        self.compile_duration = Histogram(
            "jetstream:compile_duration_seconds",
            "Wall time of first-dispatch (trace + compile + run) per bucket",
            registry=self.registry,
            buckets=(.05, .1, .25, .5, 1, 2.5, 5, 10, 30, 60, 120))

        self.moe_ffn_tokens = Counter(
            "jetstream:moe_ffn_tokens_total",
            "Rows (padded tokens) dispatched through the MoE FFN, by the form "
            "their program's shape traced to (ops/pallas_moe.use_grouped); "
            "counted on the host at dispatch, a token once a program however "
            "many expert layers it passes; empty for a dense model",
            ("form",), registry=self.registry)
        self.mla_attention_tokens = Counter(
            "jetstream:mla_attention_tokens_total",
            "Rows (padded tokens) dispatched through latent attention, by the "
            "form their program traced to (models/mla.py: a decode step is "
            "absorbed, a prefill or a prefix-continuation window expanded); "
            "counted on the host at dispatch, a token once a program and not "
            "once an attention sublayer; empty without a latent pool",
            ("form",), registry=self.registry)
        self.mla_window_attention_tokens = Counter(
            "jetstream:mla_window_attention_tokens_total",
            "Rows (padded tokens) of the prefill and prefix-continuation "
            "programs dispatched through latent attention's expanded form, "
            "by how the program keeps its scores (models/binding.bind, "
            "ModelConfig.expanded_impl): `kernel` a tile of queries against "
            "a tile of rows in VMEM (ops/pallas_dsa.py), `xla` whole in "
            "memory, [heads, queries, rows] in f32; counted on the host at "
            "dispatch, a token once a program; empty without a latent pool",
            ("form",), registry=self.registry)
        self.kv_prefill_attention_tokens = Counter(
            "jetstream:kv_prefill_attention_tokens_total",
            "Rows (padded tokens) of the prefix-continuation programs of a "
            "K/V model with window and full layers (models/llama.py, "
            "ModelConfig.kv_window: a long prompt's windows after its "
            "first), by how their attention reads the cached rows "
            "(models/binding.bind, ModelConfig.swa_impl): `kernel` one "
            "tiled kernel that walks the pages the prompt holds, the scores "
            "in VMEM (ops/pallas_paged_attention."
            "kv_window_prefill_attention), `xla` the prior table's bucket "
            "gathered whole and banded, the scores a block of queries at a "
            "time in memory; counted on the host at dispatch, a token once "
            "a program; empty for any other model",
            ("form",), registry=self.registry)
        self.ssm_scan_tokens = Counter(
            "jetstream:ssm_scan_tokens_total",
            "Rows (padded tokens) of the prefill and prefix-continuation "
            "programs of a model whose state-space layers have no matrix "
            "form (a decay a channel a state value: ModelConfig.ssm_dt_rank), "
            "by how the program runs the recurrence over a window's rows "
            "(models/binding.bind, ModelConfig.ssm_scan_impl): `kernel` the "
            "state tile resident in VMEM over the rows in order "
            "(ops/pallas_ssm.selective_scan), `xla` a scan over positions "
            "with the state through memory; counted on the host at dispatch, "
            "a token once a program; empty for any other model",
            ("form",), registry=self.registry)
        self.dsa_query_tokens = Counter(
            "jetstream:dsa_query_tokens_total",
            "Query tokens put through a block that selects the rows it "
            "attends to (learned sparse attention, models/mla.py), real lanes "
            "and prompt tokens alone: `selected` where the query's context "
            "outnumbers index_topk (its indexer's choice narrows the "
            "softmax), `all` where it attends to every row it may see; "
            "counted on the host at dispatch from positions, a token once a "
            "program; empty for a block without an indexer",
            ("form",), registry=self.registry)
        self.dsa_rows = Counter(
            "jetstream:dsa_rows_total",
            "Cached rows a layer of such a block deals with for those query "
            "tokens: `scored`, the rows a query may see (its context: what "
            "its indexer scores where it selects), and `attended`, "
            "min(context, index_topk) of them; `attended` over `scored` is "
            "the share of the context that attention reads for",
            ("kind",), registry=self.registry)
        # (Both series of each are exposed from the start, at 0.)
        for form in ("selected", "all"):
            self.dsa_query_tokens.labels(form)
        for kind in ("scored", "attended"):
            self.dsa_rows.labels(kind)
        self.swa_rows = Counter(
            "jetstream:swa_rows_total",
            "Cached rows a layer that attends to a window of the context "
            "(models/mla.py, ModelConfig.window_attn) deals with for its query "
            "tokens, real lanes and prompt tokens alone: `context`, the rows "
            "a query could see with no window (its context), and `attended`, "
            "min(context, window) of them; counted on the host at dispatch "
            "from positions, a token once a program; empty for a model "
            "without window layers",
            ("kind",), registry=self.registry)
        for kind in ("context", "attended"):
            self.swa_rows.labels(kind)
        self.ssm_tokens = Counter(
            "jetstream:ssm_tokens_total",
            "Rows (padded tokens) dispatched through the layers that keep a "
            "slot row (state-space layers, gated short convolutions), by the "
            "form their program traced to (models/hybrid.py: a decode step is "
            "the one-row `step`, a prefill or a continuation window the "
            "`scan` over a run of rows); counted on the host at dispatch, "
            "empty for a model without state layers",
            ("form",), registry=self.registry)
        self.ssm_state_updates = Counter(
            "jetstream:ssm_state_updates_total",
            "Recurrent states a dispatched decode step updates (its lanes, "
            "padding among them, times the state layers), by how the step "
            "fetches them: `kernel` in place in the state pool "
            "(ops/pallas_ssm.py), `gathered` by slot in XLA; counted on the "
            "host at dispatch by the rule the program traced with; empty "
            "where the layers keep a convolution's tail and no state",
            ("form",), registry=self.registry)
        self.ssm_slot_prefills = Counter(
            "jetstream:ssm_slot_prefills_total",
            "Slots whose rows of the state pool (recurrent state and tail, or "
            "the tail alone) a first prefill window started afresh "
            "(kvcache/state.py), warm-up programs not counted",
            registry=self.registry)
        moe_routed_pairs = Counter(
            "jetstream:moe_routed_pairs_total",
            "(Token, expert) choices of the router, padded rows among them, "
            "by whether the chosen expert is held on this chip (`yes`), "
            "would be another chip's (`no`, its part of the result left "
            "out) or computes nothing (`zero`: the token itself times its "
            "gate, on whichever chip the token is; a series only a model "
            "whose router has such outputs brings); summed on the device "
            "inside the step programs and read once tokens dispatched later "
            "have been read; empty where every choice is an expert held "
            "here", ("held",), registry=self.registry)
        self.moe_routed_pairs = {h: moe_routed_pairs.labels(held=h)
                                 for h in ("yes", "no")}
        # `zero` appears with its first count: a model without such experts
        # never exposes it.
        self.moe_zero_pairs = functools.partial(moe_routed_pairs.labels,
                                                held="zero")
        # A series appears with its first count, as `zero` does: an engine
        # whose decode programs read every held expert never exposes it.
        self.moe_decode_experts = functools.partial(Counter(
            "jetstream:moe_decode_experts_total",
            "Held experts a decode step passed, an expert once a step and "
            "expert layer, by whether the step read its weights (`yes`: a "
            "lane that is somebody's chose it, or it stood in where none "
            "was chosen) or not (`no`), where the step's program reads the "
            "chosen experts alone (ops/pallas_moe.use_chosen); summed on the "
            "device and read as moe_routed_pairs_total is; empty where "
            "decode programs read every expert they hold", ("read",),
            registry=self.registry).labels)
        # Those counts as step programs summed them on the device, oldest
        # first, each with the choices its program made in all and, of a
        # decode chunk that reads the chosen experts alone, the experts it
        # read and passed (below).
        self._pair_counts: collections.deque[
            tuple[Any, Any, int, Any, int]] = collections.deque()
        decode_chunks = Counter(
            "jetstream:decode_chunks_total",
            "Decode chunks dispatched: `ahead` while the chunk before was "
            "still unread (the device has its next work queued), `alone` "
            "with nothing in flight", ("dispatch",), registry=self.registry)
        self.decode_chunks = {d: decode_chunks.labels(dispatch=d)
                              for d in ("ahead", "alone")}
        decode_chunk_lengths = Counter(
            "jetstream:decode_chunk_lengths_total",
            "Decode chunks dispatched, by their length in steps: `full` "
            "(decode_chunk), or `short` (any length below it: "
            "decode_chunk_fractions_total says which) where a slot was "
            "open, nobody waited and the loop's own work a chunk fitted "
            "inside: an arrival then waits out a shorter chunk", ("length",),
            registry=self.registry)
        self.decode_chunk_lengths = {
            n: decode_chunk_lengths.labels(length=n)
            for n in ("short", "full")}
        decode_chunk_fractions = Counter(
            "jetstream:decode_chunk_fractions_total",
            "The same chunks by the fraction of decode_chunk they ran: "
            "`quarter` (the shortest the loop asks for: its own measured "
            "work a period fitted inside a quarter's reckoned time with "
            "KEEP_UP_S to spare), `half` (it fitted a half and not a "
            "quarter) or `full`", ("fraction",), registry=self.registry)
        self.decode_chunk_fractions = {
            n: decode_chunk_fractions.labels(fraction=n)
            for n in ("quarter", "half", "full")}
        slot_refills = Counter(
            "jetstream:slot_refills_total",
            "Requests admitted into an engine slot: `ahead` of the booking "
            "of the slot's last request, known to end inside the chunk in "
            "flight (the prefill queues behind that chunk and the slot's "
            "lane is in the next), or `after` it, into an empty slot",
            ("when",), registry=self.registry)
        self.slot_refills = {w: slot_refills.labels(when=w)
                             for w in ("ahead", "after")}
        kv_table_groups = Counter(
            "jetstream:kv_table_groups_total",
            "Groups of RUN_PAGES entries of the block tables handed to "
            "admitted requests, as the paged decode kernels of either "
            "family walk them at full length "
            "(ops/pallas_latent_attention.stage_fetch): `run` where a group "
            "names adjacent blocks in ascending order (one copy), `split` "
            "otherwise (a copy a page; a short last group is one); counted "
            "on the host at admission",
            ("kind",), registry=self.registry)
        self.kv_table_groups = {k: kv_table_groups.labels(kind=k)
                                for k in ("run", "split")}
        kv_window_table_groups = Counter(
            "jetstream:kv_window_table_groups_total",
            "Groups of RUN_PAGES entries of the WINDOW tables of decoding "
            "lanes, as the window layers' decode kernels cut and walk them "
            "(ops/attention.window_table from an aligned entry, "
            "ops/pallas_latent_attention.table_runs): `run` where a group "
            "lies inside the lane's cached pages and names adjacent blocks "
            "in ascending order (one copy), `split` otherwise (a copy a "
            "page; the short last group a lane is writing into is one); "
            "counted on the host once a lane a dispatched decode chunk, at "
            "the chunk's first position, where the window pool's owner fills "
            "the row (engine/blocks.WindowedAllocator.slide); nothing on an "
            "engine without window layers",
            ("kind",), registry=self.registry)
        self.kv_window_table_groups = {
            k: kv_window_table_groups.labels(kind=k) for k in ("run", "split")}
        admissions = Counter(
            "jetstream:admissions_total",
            "Requests admitted into an engine slot: woken by the arrival "
            "inside a `hold` of the next decode chunk (the prefill queues "
            "behind the chunk that runs alone, ahead of the next), or at the "
            "top of a `step` of the loop", ("at",), registry=self.registry)
        self.admissions = {a: admissions.labels(at=a)
                           for a in ("hold", "step")}
        self.decode_lanes_discarded = Counter(
            "jetstream:decode_lanes_discarded_total",
            "Lanes of a chunk thrown away whole: the request ended (a stop "
            "token, an abort) in the chunk before, with this one in flight",
            registry=self.registry)
        self.prompt_tokens = Counter("jetstream:prompt_tokens_total", "Prefilled tokens",
                                     registry=self.registry)
        self.prefix_cached_tokens = Counter(
            "jetstream:prefix_cached_tokens_total",
            "Prompt tokens served from the prefix cache", registry=self.registry)
        # Prefix-reuse observability pair (docs/observability.md §KV-cache
        # observability): incremented TOGETHER at prefill admission — one
        # point, one request, once — so hit/total is a per-pod actual hit
        # ratio the router's /debug/kv can derive from two scraped counters.
        # (prompt_tokens/prefix_cached_tokens above count COMPUTE-side work:
        # suffix tokens per dispatch, window chunks separately — a ratio of
        # those two mixes accounting bases.)
        self.prefill_tokens_admitted = Counter(
            "jetstream:prefill_tokens",
            "Prompt tokens admitted to prefill (cache hits + computed), "
            "counted once per request at admission", registry=self.registry)
        self.prefix_hit_tokens = Counter(
            "jetstream:prefix_hit_tokens",
            "Prompt tokens covered by the prefix cache at prefill admission "
            "(the engine-confirmed actual behind x-kv-hit-tokens)",
            registry=self.registry)
        self.generation_tokens = Counter("jetstream:generation_tokens_total", "Decoded tokens",
                                         registry=self.registry)
        self.ttft = Histogram("jetstream:time_to_first_token_seconds", "TTFT",
                              registry=self.registry,
                              buckets=(.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10))
        self.request_success = Counter("jetstream:request_success_total", "Finished requests",
                                       ("finished_reason",), registry=self.registry)
        # A request's wait, split where it happens: submit() -> the first
        # admission pop, and that pop -> the first token landing. Observed
        # once each per request, streamed or not; their sum is the TTFT
        # above less the hop from the request's construction to submit().
        self.queue_wait = Histogram(
            "jetstream:queue_wait_seconds",
            "submit() to the request's first admission pop",
            registry=self.registry, buckets=_WAIT_BUCKETS)
        self.admit_to_first_token = Histogram(
            "jetstream:admit_to_first_token_seconds",
            "First admission pop to the first token landing on the host",
            registry=self.registry, buckets=_WAIT_BUCKETS)
        loop_seconds = Counter(
            "jetstream:engine_loop_seconds_total",
            "Wall time of the engine loop by phase (the engine.<phase> host "
            "spans of a profiler trace, always on)", ("phase",),
            registry=self.registry)
        self.loop_seconds = {p: loop_seconds.labels(phase=p) for p in LOOP_PHASES}
        loop_stall_seconds = Counter(
            "jetstream:loop_stall_seconds_total",
            "Seconds by which stalled periods of the engine loop (a decode "
            "chunk, readback to readback, over 3x the median of the last 32 "
            "and over it by 0.5 s) exceeded that median: `device_wait` the "
            "part the loop was blocked on the device for, beyond that part's "
            "own median, `host` the rest; each stall is a record of "
            "/debug/stalls", ("where",), registry=self.registry)
        self.loop_stall_seconds = {w: loop_stall_seconds.labels(where=w)
                                   for w in STALL_WHERE}
        self.event_loop_lag = Histogram(
            "jetstream:event_loop_lag_seconds",
            "Lag of the engine server's event loop, which writes every "
            "streamed token: the overshoot of a 100 ms sleep (the router's "
            "LoopLagMonitor on this loop)",
            registry=self.registry, buckets=LOOP_LAG_BUCKETS)

    def book_program(self, counts) -> None:
        """Book what a model family says a dispatched program runs as
        (Bound.program_counts): (one of PROGRAM_COUNTERS, its label's value
        or None, the amount) each."""
        for name, label, amount in counts:
            assert name in PROGRAM_COUNTERS, name
            counter = getattr(self, name)
            (counter if label is None else counter.labels(label)).inc(amount)

    def keep_pair_counts(self, held, zero, pairs: int, read=None,
                         visits: int = 0) -> None:
        """Queue one step's counts of its router's choices, still on the
        device (``held`` None: its cache carries none; ``zero`` None: no
        such outputs), with the ``pairs`` its rows made in all; ``read`` of
        ``visits`` held experts where its program reads the chosen ones
        alone (else None)."""
        if held is None:
            return
        for count in (held, zero, read):
            if count is not None:
                count.copy_to_host_async()
        self._pair_counts.append((held, zero, pairs, read, visits))

    def book_pair_counts(self) -> None:
        """Book the queued counts whose programs are done (every one
        dispatched before tokens the host has just read is): reading them
        waits for nothing."""
        while self._pair_counts and self._pair_counts[0][0].is_ready():
            held, zero, pairs, read, visits = self._pair_counts.popleft()
            held, zero = int(held), 0 if zero is None else int(zero)
            self.moe_routed_pairs["yes"].inc(held)
            self.moe_routed_pairs["no"].inc(pairs - held - zero)
            if zero:
                self.moe_zero_pairs().inc(zero)
            if read is not None:
                self.moe_decode_experts(read="yes").inc(int(read))
                self.moe_decode_experts(read="no").inc(visits - int(read))

    def watch_xla_builds(self) -> None:
        """Count the programs JAX builds from now on, and show the count
        (engines that run JAX call this; the simulator never imports it)."""
        self.registry.register(XLA_BUILDS.watch())

    def observe_allocator(self, allocator) -> None:
        """One-call snapshot of the allocator's occupancy gauges — used at
        every alloc/free site so usage, free-list depth, and parked cache
        size can never drift apart."""
        self.kv_usage.set(allocator.used_fraction)
        self.free_blocks.set(allocator.free_blocks)
        self.cached_blocks.set(getattr(allocator, "cached_block_count", 0))
        self.kv_window_usage.set(
            getattr(allocator, "window_used_fraction", 0.0))

    def render(self) -> bytes:
        return generate_latest(self.registry)


class LoopStalls:
    """The engine loop's periods judged one by one (engine/core.py
    `_land_chunk`): a running median of the last STALL_WINDOW periods and of
    the part of each the loop was blocked on the device for; a period far
    beyond its median is a stall, whose excess goes to
    jetstream:loop_stall_seconds_total{where} and whose record, the seconds
    of every phase in it, goes onto `ring` (`GET /debug/stalls`). Written by
    the engine thread, read by server handlers: GIL-atomic deque ops."""

    def __init__(self, telemetry: EngineTelemetry):
        self._seconds = telemetry.loop_stall_seconds
        self._periods: collections.deque[float] = \
            collections.deque(maxlen=STALL_WINDOW)
        self._blocked: collections.deque[float] = \
            collections.deque(maxlen=STALL_WINDOW)
        self.ring: collections.deque[dict[str, Any]] = \
            collections.deque(maxlen=STALL_RING)

    def note(self, period: float, phases: dict[str, float],
             **chunk: Any) -> dict[str, Any] | None:
        """One timed period and the seconds of each loop phase inside it;
        `chunk` is what the record says besides (the loop's clock, lanes,
        prefills). Returns the record if the period was a stall."""
        blocked = phases["decode_wait"]
        record = None
        # The first test is the third's, without a median: most periods of
        # most deployments end here.
        if (period > STALL_MIN_EXCESS_S
                and len(self._periods) >= STALL_MIN_PERIODS):
            median = statistics.median(self._periods)
            excess = period - median
            if period > STALL_RATIO * median and excess > STALL_MIN_EXCESS_S:
                blocked_median = statistics.median(self._blocked)
                device = min(max(blocked - blocked_median, 0.0), excess)
                host = excess - device
                self._seconds["device_wait"].inc(device)
                self._seconds["host"].inc(host)
                record = {
                    "unix": round(time.time(), 3), **chunk,
                    "period_s": period, "median_s": median,
                    "blocked_s": blocked, "blocked_median_s": blocked_median,
                    "excess_s": {"device_wait": device, "host": host},
                    "phases_s": {**phases, "none": max(
                        period - sum(phases.values()), 0.0)}}
                self.ring.append(record)
        self._periods.append(period)
        self._blocked.append(blocked)
        return record


class PrefixHitLog:
    """Per-request ACTUAL prefix-hit accounting, shared by the real engine
    and the sim so the two cannot drift: each prefill admission records its
    engine-confirmed hit depth exactly once into

    - ``stats`` (request_id → record), popped by the server for the
      ``x-kv-hit-blocks`` / ``x-kv-hit-tokens`` response headers and read
      for ``usage.prompt_tokens_details``;
    - ``ring``, the bounded newest-last view behind engine ``/debug/kv``;
    - ``totals`` + the ``jetstream:prefill_tokens`` /
      ``jetstream:prefix_hit_tokens`` counter pair (incremented together,
      so hit/total is the pod's cumulative actual hit ratio).

    ``kind="probe"`` marks a shared-storage cache_hit_threshold probe that
    bailed with CACHE_THRESHOLD: it lands in the ring (the probe verdict is
    worth seeing) but NOT in the admitted-token counters — no prefill
    happened, and the retry after the remote prefill leg is counted when it
    does. Written by the serving thread, read by server handlers:
    individually GIL-atomic dict/deque ops."""

    RING_CAP = 512

    def __init__(self, telemetry: EngineTelemetry, block_size: int,
                 ring_cap: int = RING_CAP):
        self.telemetry = telemetry
        self.block = max(block_size, 1)
        self.stats: dict[str, dict[str, Any]] = {}
        self._order: collections.deque[str] = collections.deque()
        self.ring: collections.deque[dict[str, Any]] = \
            collections.deque(maxlen=ring_cap)
        self.totals = {"requests": 0, "prefill_tokens": 0,
                       "prefix_hit_tokens": 0}

    def note(self, request_id: str, hit_tokens: int, prompt_tokens: int, *,
             kind: str = "prefill") -> dict[str, Any]:
        rec = {"request_id": request_id, "kind": kind,
               "hit_tokens": int(hit_tokens),
               "hit_blocks": int(hit_tokens) // self.block,
               "prompt_tokens": int(prompt_tokens),
               "unix": round(time.time(), 3)}
        if kind == "prefill":
            self.telemetry.prefill_tokens_admitted.inc(prompt_tokens)
            self.totals["requests"] += 1
            self.totals["prefill_tokens"] += int(prompt_tokens)
            if hit_tokens:
                self.telemetry.prefix_hit_tokens.inc(hit_tokens)
                self.totals["prefix_hit_tokens"] += int(hit_tokens)
        # A re-dispatched request id overwrites its entry instead of minting
        # a duplicate ring slot (the _note_kv_import dedup discipline: a
        # stale first occurrence reaching the front must not evict the live
        # entry).
        if request_id not in self.stats:
            self._order.append(request_id)
        self.stats[request_id] = rec
        while len(self._order) > self.ring.maxlen:
            self.stats.pop(self._order.popleft(), None)
        self.ring.append(rec)
        return rec

    def pop(self, request_id: str) -> dict[str, Any] | None:
        return self.stats.pop(request_id, None)

    def get(self, request_id: str) -> dict[str, Any] | None:
        return self.stats.get(request_id)
