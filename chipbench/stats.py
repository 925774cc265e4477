"""Arithmetic from client records to end-to-end metrics. Pure functions."""

from __future__ import annotations

import math


def percentile(values: list[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default), p in
    [0, 100]. Raises on an empty list: a metric with no sample is a fault."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def measured(records: list, seconds: float) -> list:
    """The window's requests: those DUE in [0, seconds), whenever they ended.
    Requests of the ramp (due before 0) load the system and are not counted."""
    return [r for r in records if 0.0 <= r.due_s < seconds]


def tokens_in_window(records: list, seconds: float) -> int:
    """Output tokens that ARRIVED in [0, seconds], from any request: warm-up
    before and drain after excluded."""
    return sum(n for r in records for t, n in r.pieces if 0.0 <= t <= seconds)


def end_to_end(records: list, seconds: float, chips: int) -> dict[str, float]:
    """Every end-to-end quantity the benchmark knows, over all requests of
    the window; the caller keeps the ones the cell reports. A failed request
    has no time of its own and is counted in `failed`, not hidden in a tail
    (a run with any failure is not `correct`)."""
    rows = [r for r in measured(records, seconds) if r.ok]
    ttft = [r.ttft_s * 1e3 for r in rows]
    tpot = [r.tpot_s * 1e3 for r in rows if r.tpot_s is not None]
    out = {"out_tokens_per_s":
           tokens_in_window(records, seconds) / seconds / chips}
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50)
        out["ttft_p95_ms"] = percentile(ttft, 95)
        out["ttft_mean_ms"] = sum(ttft) / len(ttft)
    # The engines' TTFT histogram counts first tokens that HAPPENED in the
    # window, whenever the request was due; the same population, for the
    # difference of the two means.
    same = [r.ttft_s * 1e3 for r in records
            if r.ok and 0.0 <= r.first_s < seconds]
    if same:
        out["ttft_mean_first_token_in_window_ms"] = sum(same) / len(same)
    if tpot:
        out["tpot_p95_ms"] = percentile(tpot, 95)
        out["tpot_p50_ms"] = percentile(tpot, 50)
    return out


def generator_report(records: list, seconds: float) -> dict:
    """How late the generator ran and how many samples stand behind the
    tails, so a starved generator is not read as a fast server."""
    rows = measured(records, seconds)
    late = [(r.sent_s - r.due_s) * 1e3 for r in rows]
    n_ok = sum(r.ok for r in rows)
    return {"requests_in_window": len(rows), "ok_in_window": n_ok,
            "samples_beyond_p95": int(n_ok * 0.05),
            "late_p50_ms": percentile(late, 50) if late else None,
            "late_p99_ms": percentile(late, 99) if late else None,
            "late_max_ms": max(late) if late else None,
            "ramp_requests": sum(r.due_s < 0 for r in records)}
