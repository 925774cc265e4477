"""Decode attention over a window of K/V pages, its share of its roofline
(%): trace_swa_op_time.py's quantity for the kernel whose counts are
kernels_swa_kv.py's (the configuration's `sliding_window_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`).

Device time: the summed durations of the trace's operations whose name
matches `op_regex`, or whose detail does and is a custom call (an operation
that takes the kernel's result names it in its detail and is not counted).
The least time: calls x max(FLOPs/peak, bytes/peak). Contexts are the
client's view, as trace_op_time.py takes them. Nothing without a device
trace, where no such operation ran in the slice (a program without the
kernel: the parent's, or another configuration's), or for a configuration
whose window layers keep no K/V (a latent cache, or no window at all)."""

import importlib.util
import os
import re

import kernels
import kernels_swa_kv


def _contexts_in_slice():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "trace_op_time.py")
    spec = importlib.util.spec_from_file_location("chipbench_trace_op_time", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.contexts_in_slice


def read(spec, ctx):
    if not ctx.traces or ctx.trace_span is None:
        return None
    model = ctx.model.get("text_config", ctx.model)
    if not model.get("sliding_window_size") or model.get("kv_lora_rank"):
        return None
    pattern = re.compile(spec["op_regex"])
    calls, seconds = 0, 0.0
    for trace in ctx.traces:
        for dev in trace.get("devices", []):
            for name, row in dev["ops"].items():
                detail = row.get("detail", "")
                if pattern.search(name) or (detail.startswith("custom-call")
                                            and pattern.search(detail)):
                    calls += row["count"]
                    seconds += row["seconds"]
    if not calls or seconds <= 0:
        return None
    context_tokens, lanes = _contexts_in_slice()(ctx.records, ctx.trace_span)
    replicas = max(len(ctx.traces), 1)
    context_tokens, lanes = context_tokens / replicas, lanes / replicas
    head_dim = (model.get("head_dim")
                or model["hidden_size"] // model["num_attention_heads"])
    cost = kernels_swa_kv.window_kv_attention_decode(
        context_tokens, lanes, model["sliding_window_size"],
        model["num_attention_heads"], model["num_key_value_heads"], head_dim)
    least, bound = kernels.roofline_seconds(cost, ctx.device_kind)
    ctx.notes["swa_kv_decode"] = {
        "calls": calls, "kernel_seconds": seconds, "bound": bound,
        "mean_context_tokens_per_call": context_tokens,
        "mean_lanes": lanes, "least_seconds_per_call": least}
    return 100.0 * calls * least / seconds
