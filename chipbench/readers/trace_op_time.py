"""A kernel's share of its roofline (%): the least time the chip could take
for the calls in the traced slice, over the device time they took.

Device time: the summed durations of the trace's operations whose name or
detail matches `op_regex`. The least time: calls x max(FLOPs/peak,
bytes/peak), with FLOPs and bytes from the shape function in kernels.py. The
trace does not carry the sequences' lengths, so the contexts are the client's
view: for each instant of the slice, the requests then between their first
and last token, each with its prompt plus the tokens received so far (at most
one decode chunk behind the device)."""

import re

import kernels


def contexts_in_slice(records, span, points=200):
    """Time-averaged (sum of contexts, lanes) over the slice."""
    a, b = span
    ctx_sum = lanes_sum = 0.0
    for i in range(points):
        t = a + (b - a) * (i + 0.5) / points
        for r in records:
            if r.first_s is None or r.last_s is None \
                    or not r.first_s <= t < r.last_s:
                continue
            got = sum(n for at, n in r.pieces if at <= t)
            ctx_sum += (r.prompt_tokens or r.prompt_tokens_meant) + got
            lanes_sum += 1
    return ctx_sum / points, lanes_sum / points


def read(spec, ctx):
    if not ctx.traces or ctx.trace_span is None:
        return None
    pattern = re.compile(spec["op_regex"])
    calls, seconds = 0, 0.0
    for trace in ctx.traces:
        for dev in trace.get("devices", []):
            for name, row in dev["ops"].items():
                if pattern.search(name) or pattern.search(row.get("detail", "")):
                    calls += row["count"]
                    seconds += row["seconds"]
    if not calls or seconds <= 0:
        return None
    context_tokens, lanes = contexts_in_slice(ctx.records, ctx.trace_span)
    replicas = max(len(ctx.traces), 1)
    m = ctx.model
    head_dim = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    cost = kernels.SHAPE_FUNCTIONS[spec["shape_function"]](
        context_tokens / replicas, lanes / replicas, m["num_attention_heads"],
        m["num_key_value_heads"], head_dim)
    least, bound = kernels.roofline_seconds(cost, ctx.device_kind)
    ctx.notes[spec["shape_function"]] = {
        "calls": calls, "kernel_seconds": seconds, "bound": bound,
        "mean_context_tokens_per_call": context_tokens / replicas,
        "mean_lanes": lanes / replicas, "least_seconds_per_call": least}
    return 100.0 * calls * least / seconds
