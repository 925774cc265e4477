"""Which `order` of a closed-loop mix's cycle gives every seed the same window.

`chipbench/traffic.py` walks a mix's `pool` shapes in one fixed cyclic order
(the shuffle named by the file's `order` key) and the seed picks the start. A
window of `deepseek-v3.2-exp-cut.longctx-reason` holds ~33 of its 48 shapes,
so the start decides how much prefill falls inside it: under the default
shuffle `out_tokens_per_s` ran 707-781 over the starts and the cell was
refused as too noisy (PERF.md section 6, PR 41, second round).

This is a model of `TpuEngine`'s loop in DEVICE TIME, fed with the chip's
per-program times from PERF.md section 5: a count, not a measurement. A step
of the loop admits into the empty slots and into those the chunk in flight
vacates, writes at most min(slots prefilling, 4) windows for the oldest
requests, dispatches the next chunk of 8 decode steps and lands the one
before. It reads `out_tokens_per_s` and `tpot_p95_ms` as `chipbench/stats.py`
does, for every start of an order.

    python scripts/longctx_order_model.py --order 659469          # the 48 starts
    python scripts/longctx_order_model.py --search 0 1000000      # rank shuffles

The search ranks shuffles by a proxy (how far any run of 4-24 consecutive
shapes strays from the mean in prefill cost, output length and their product;
a million take a minute on eight cores), runs the best `--keep` through the
model (0.7 s each a core) and prints them by the spread of their worse
metric. Never write a number from here under a device metric.
"""

from __future__ import annotations

import argparse
import math
import multiprocessing
import os
import random
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
import traffic  # noqa: E402

# The chip's times, ms, a mix (`use`): longctx-reason's from my chip runs,
# PR 41, `--trace 2 --dump-trace`; longctx-16k's below; longctx-wide's from
# my chip run, PR 45
# (seed 3000000011: 40 decode steps of ~57 lanes at ~10k in 0.6527 s, of
# which the two full layers' attention and indexer 3.7 ms a step; the first
# window 16.6, continuations 29.5 / 33.5 / 37.7 / 42.5 / 59.5 by bucket).
TIMES = {
    "longctx-reason": dict(
        FIRST_WINDOW_MS=37.0,
        CONTINUATION_MS={64: 40.0, 128: 50.1, 256: 59.7, 512: 59.7,
                         1024: 75.0},
        ROW_MS=6.0, ROW_FROM=4, STEP_MS=14.6, STEP_MS_PER_TOKEN=10.0 / 286e3,
        LANES=32),
    "longctx-wide": dict(
        FIRST_WINDOW_MS=16.6,
        CONTINUATION_MS={64: 29.5, 128: 33.5, 256: 37.7, 512: 39.5,
                         1024: 47.0},
        ROW_MS=2.4, ROW_FROM=4, STEP_MS=12.6, STEP_MS_PER_TOKEN=3.7 / 570e3,
        LANES=64),
    # my chip runs, PR 48, `--trace 2 --dump-trace` (seeds 3000000011,
    # 2148000101, 2148000404): 47 decode steps of ~28 lanes at ~8.1k in
    # 0.828 s, 17.6 ms a step: the weights' reads, head and sampler 10.4 ms
    # whatever the lanes, the six window layers' walks 4.40 ms = 0.155 ms a
    # lane (4,096 rows each whatever the context), the two full layers' 2.81
    # ms for 231k rows; the first window 22.8, continuations 27.5 / 27.6 /
    # 28.0 / 35.9 / 44.1 by bucket, whatever the rows written (XLA attends
    # over the whole padded bucket). The ramp decodes at 2-16 lanes, where
    # a step is 11-13 ms: without the lane's term the model reaches the
    # window's start seconds late. 10.1 and not the trace's 10.4: fitted
    # to seven runs' `out_tokens_per_s` and `tpot_p95_ms` (six starts of
    # `order` 37059, one of 0), which the model then reads within 0.5%.
    # `DRAWN_AS_CLIENT`: this mix's shapes go to arrivals as
    # `chipbench/client.run_chain` hands them out (`simulate`); the two
    # older mixes keep the assignment their orders were pinned under, until
    # a `benchmark` PR pins them anew (PERF.md section 7 (95)). `STRAY_RUNS`:
    # a window of this mix admits ~60 consecutive shapes of 96, and what it
    # leaves out is a run of ~36.
    "longctx-16k": dict(
        FIRST_WINDOW_MS=22.8,
        CONTINUATION_MS={64: 27.5, 128: 27.6, 256: 28.0, 512: 35.9,
                         1024: 44.1},
        ROW_MS=0.0, ROW_FROM=4, STEP_MS=10.1, STEP_MS_PER_LANE=0.155,
        STEP_MS_PER_TOKEN=2.81 / 231e3, LANES=32, DRAWN_AS_CLIENT=True,
        STRAY_RUNS=(4, 8, 12, 16, 20, 24, 32, 36, 48, 60)),
}
# What a mix that states none of them takes: a step's cost a lane is in its
# `STEP_MS`, a shape goes to each request in arrival order, and the search's
# proxy looks at runs of 4-24 consecutive shapes.
DEFAULTS = dict(STEP_MS_PER_LANE=0.0, DRAWN_AS_CLIENT=False,
                STRAY_RUNS=(4, 8, 12, 16, 20, 24))
WINDOW, CHUNK, STEP_WINDOWS = 1024, 8, 4


def use(mix_name: str) -> None:
    """Take `mix_name`'s times and lanes as the module's."""
    globals().update({**DEFAULTS, **TIMES[mix_name]})


use("longctx-reason")


def window_ms(written_k: int) -> float:
    """One prefill window over `written_k` thousand rows already written."""
    if written_k == 0:
        return FIRST_WINDOW_MS
    bucket = 64
    while bucket < written_k * 64:
        bucket *= 2
    return (CONTINUATION_MS[min(bucket, 1024)]
            + ROW_MS * max(written_k - ROW_FROM, 0))


def shapes(mix: dict, order) -> tuple[list[int], list[int]]:
    rng = random.Random(f"chipbench/order/{mix['kind']}/{order}")
    pool = int(mix["pool"])
    return (traffic.grid(mix["prompt_tokens"], pool, rng),
            traffic.grid(mix["output_tokens"], pool, rng))


def percentile(values: list[float], p: float) -> float:
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def simulate(mix: dict, prompts: list[int], outputs: list[int], offset: int,
             seconds: float) -> dict:
    """One run from the ramp to the last answer; times in seconds from the
    window's start."""
    ramp, clients, pool = mix["ramp_s"], mix["clients"], len(prompts)
    # Under `DRAWN_AS_CLIENT` every client draws its first shape before the
    # first is due (the k-th client the k-th of the cycle: `client.run_chain`
    # starts all its tasks at once) and a later request takes the next shape
    # when the one before it ends; without it, a request takes the next shape
    # as it arrives.
    pending = sorted((-ramp + ramp * c / clients, c, c) for c in range(clients))
    drawn = clients
    waiting, requests, pieces = [], [], []
    slots: list[dict | None] = [None] * LANES
    inflight = None                  # (lanes, end of the chunk)
    now = -ramp

    def arrive() -> None:
        while pending and pending[0][0] <= now:
            due, client, k = pending.pop(0)
            if due >= seconds:       # nothing is due after the window's end
                continue
            i = (offset + (k if DRAWN_AS_CLIENT else len(requests))) % pool
            req = dict(due=due, prompt=prompts[i], out=outputs[i], client=client,
                       written=0, made=0, ahead=0, prefilling=True,
                       first_token_due=False, first=None, last=None)
            waiting.append(req)
            requests.append(req)

    def made(s: dict) -> int:
        return 1 if s["first_token_due"] else s["made"]

    def finish(s: dict) -> None:
        nonlocal drawn
        s["last"] = now
        pending.append((now + 0.002, s["client"], drawn))   # the client's next
        drawn += 1
        pending.sort()

    while True:
        arrive()
        if not waiting and not any(slots) and inflight is None:
            if not pending or pending[0][0] >= seconds:
                break
            now = max(now, pending[0][0])
            continue
        empty = [i for i, s in enumerate(slots) if s is None]
        vacating = [i for i, s in enumerate(slots)
                    if s is not None and s["ahead"] and not s["prefilling"]
                    and s["made"] + s["ahead"] >= s["out"]]
        for i in empty + vacating:
            if not waiting:
                break
            slots[i] = waiting.pop(0)
        oldest = sorted((s["due"], i) for i, s in enumerate(slots)
                        if s is not None and s["prefilling"])
        budget, windows_ms, first_tokens = min(len(oldest), STEP_WINDOWS), 0.0, []
        for _, i in oldest:
            s = slots[i]
            while budget and s["prefilling"]:
                windows_ms += window_ms(s["written"] // WINDOW)
                s["written"] = min(s["written"] + WINDOW, s["prompt"])
                budget -= 1
                if s["written"] >= s["prompt"]:
                    s["prefilling"], s["first_token_due"] = False, True
                    first_tokens.append(s)
        lanes = [s for s in slots if s is not None and not s["prefilling"]
                 and made(s) + s["ahead"] < s["out"]]
        chunk_ms = 0.0
        if lanes:
            context = sum(s["prompt"] + made(s) + s["ahead"] + CHUNK // 2
                          for s in lanes)
            chunk_ms = CHUNK * (STEP_MS + STEP_MS_PER_TOKEN * context
                                + STEP_MS_PER_LANE * len(lanes))
            for s in lanes:
                s["ahead"] += CHUNK
        if inflight is not None:     # land the chunk before
            landed, end = inflight
            now = max(now, end)
            for s in landed:
                s["ahead"] -= CHUNK
                if s["last"] is not None:
                    continue
                n = min(CHUNK, s["out"] - s["made"])
                s["made"] += n
                pieces.append((now, n))
                if s["made"] >= s["out"]:
                    finish(s)
                    slots[:] = [None if x is s else x for x in slots]
        now += windows_ms / 1e3      # the windows ran behind that chunk
        for s in first_tokens:
            s["first"], s["made"], s["first_token_due"] = now, 1, False
            pieces.append((now, 1))
        inflight = (lanes, now + chunk_ms / 1e3) if lanes else None
        if inflight is None and not windows_ms:
            if pending and pending[0][0] < seconds:
                now = max(now, pending[0][0])
            elif not waiting and not any(slots):
                break
    rows = [r for r in requests if 0.0 <= r["due"] < seconds]
    tpot = [(r["last"] - r["first"]) * 1e3 / (r["out"] - 1) for r in rows]
    return {"out_tokens_per_s":
            sum(n for t, n in pieces if 0.0 <= t <= seconds) / seconds,
            "tpot_p95_ms": percentile(tpot, 95), "requests": len(rows)}


def starts(mix: dict, order, seconds: float) -> list[dict]:
    prompts, outputs = shapes(mix, order)
    return [simulate(mix, prompts, outputs, offset, seconds)
            for offset in range(len(prompts))]


def relative_sd(values: list[float]) -> float:
    return statistics.pstdev(values) / statistics.mean(values)


# ---- the search -------------------------------------------------------------

def strays(values: list[float]) -> float:
    """The largest deviation from the mean of any run of consecutive shapes
    (cyclic), over the mix's `STRAY_RUNS` run lengths, as a share of one
    cycle's sum."""
    mean = sum(values) / len(values)
    twice = [v - mean for v in values] * 2
    sums = [0.0]
    for v in twice:
        sums.append(sums[-1] + v)
    worst = max(abs(sums[k + n] - sums[k])
                for n in STRAY_RUNS for k in range(len(values)))
    return worst / sum(values)


def proxy(args) -> tuple[float, int]:
    mix, order = args
    prompts, outputs = shapes(mix, order)
    cost = [sum(window_ms(k) for k in range(-(-p // WINDOW))) for p in prompts]
    return max(strays(cost), strays(outputs),
               strays([p * o for p, o in zip(prompts, outputs)])), order


def _score(args) -> tuple[float, float, float, int]:
    mix, order, seconds = args
    runs = starts(mix, order, seconds)
    tok = relative_sd([r["out_tokens_per_s"] for r in runs])
    tpot = relative_sd([r["tpot_p95_ms"] for r in runs])
    return max(tok / 0.02, tpot / 0.025), tok, tpot, order


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mix", default="longctx-reason")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--order", type=int, default=None,
                    help="print this order's 48 starts (default: the file's)")
    ap.add_argument("--search", type=int, nargs=2, metavar=("LO", "HI"))
    ap.add_argument("--keep", type=int, default=6000)
    args = ap.parse_args()
    use(args.mix)
    mix = traffic.load_mix(traffic.mix_path(ROOT, args.mix))
    if args.search is None:
        order = mix.get("order", 0) if args.order is None else args.order
        runs = starts(mix, order, args.seconds)
        for offset, r in enumerate(runs):
            print(offset, round(r["out_tokens_per_s"], 1),
                  round(r["tpot_p95_ms"], 2), r["requests"])
        for name in ("out_tokens_per_s", "tpot_p95_ms"):
            xs = [r[name] for r in runs]
            print(f"order {order} {name}: {min(xs):.2f}-{max(xs):.2f}, median "
                  f"{statistics.median(xs):.2f}, sd {relative_sd(xs):.4f}")
        return 0
    with multiprocessing.Pool() as pool:
        ranked = sorted(pool.imap_unordered(
            proxy, ((mix, o) for o in range(*args.search)), chunksize=512))
        jobs = [(mix, o, args.seconds) for _, o in ranked[: args.keep]]
        scored = sorted(pool.imap_unordered(_score, jobs, chunksize=4))
    for worse, tok, tpot, order in scored[:20]:
        print(f"order {order}: sd out_tokens_per_s {tok:.4f} tpot_p95_ms "
              f"{tpot:.4f} (worse of the two over half its bound: {worse:.2f})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
