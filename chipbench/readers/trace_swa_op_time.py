"""Decode attention over a window of a latent cache, its share of its
roofline (%): trace_dsa_op_time.py's quantity for the kernel whose counts are
kernels_swa.py's (the configuration's `sliding_window_size`,
`swa_num_attention_heads`, `swa_kv_lora_rank` + `swa_qk_rope_head_dim` values
a row).

Device time: the summed durations of the trace's operations whose name
matches `op_regex`, or whose detail does and is a custom call (an operation
that takes the kernel's result names it in its detail and is not counted).
The least time: calls x max(FLOPs/peak, bytes/peak). Contexts are the
client's view, as trace_op_time.py takes them. Nothing without a device
trace, where no such operation ran in the slice (a program without the
kernel: the parent's, or another configuration's), or for a configuration
without window layers."""

import importlib.util
import os
import re

import kernels
import kernels_swa


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
    if not model.get("sliding_window_size"):
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
    cost = kernels_swa.window_attention_decode(
        context_tokens, lanes, model["sliding_window_size"],
        model["swa_num_attention_heads"],
        model["swa_kv_lora_rank"] + model["swa_qk_rope_head_dim"],
        model["swa_kv_lora_rank"])
    least, bound = kernels.roofline_seconds(cost, ctx.device_kind)
    ctx.notes["swa_decode"] = {
        "calls": calls, "kernel_seconds": seconds, "bound": bound,
        "mean_context_tokens_per_call": context_tokens,
        "mean_lanes": lanes, "least_seconds_per_call": least}
    return 100.0 * calls * least / seconds
