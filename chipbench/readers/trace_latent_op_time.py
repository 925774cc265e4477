"""The latent (MLA) decode-attention kernel's share of its roofline (%):
trace_op_time.py's quantity for a kernel whose shapes come from other keys of
the configuration (`kv_lora_rank`, `qk_rope_head_dim`, under `text_config`
where the published file nests them) and whose counts are kernels_mla.py's.

Device time: the summed durations of the trace's operations whose name or
detail matches `op_regex`. The least time: calls x max(FLOPs/peak,
bytes/peak). Contexts are the client's view, as trace_op_time.py takes them
(that module's `contexts_in_slice`, loaded from its file). Nothing without a
device trace, where no such operation ran in the slice (a program without the
kernel), or for a configuration without a latent cache."""

import importlib.util
import os
import re

import kernels
import kernels_mla


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
    if not model.get("kv_lora_rank"):
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
    context_tokens, lanes = _contexts_in_slice()(ctx.records, ctx.trace_span)
    replicas = max(len(ctx.traces), 1)
    cost = kernels_mla.latent_attention_decode(
        context_tokens / replicas, lanes / replicas,
        model["num_attention_heads"],
        model["kv_lora_rank"] + model["qk_rope_head_dim"],
        model["kv_lora_rank"])
    least, bound = kernels.roofline_seconds(cost, ctx.device_kind)
    ctx.notes["latent_attention_decode"] = {
        "calls": calls, "kernel_seconds": seconds, "bound": bound,
        "mean_context_tokens_per_call": context_tokens / replicas,
        "mean_lanes": lanes / replicas, "least_seconds_per_call": least}
    return 100.0 * calls * least / seconds
