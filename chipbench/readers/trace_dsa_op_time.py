"""A kernel of learned sparse attention's share of its roofline (%), at
decode: trace_latent_op_time.py's quantity for the two kernels whose counts
are kernels_dsa.py's. `cost` names which: "indexer" (the configuration's
`index_n_heads`, `index_head_dim`) or "attention" (`index_topk` rows a lane at
most, `kv_lora_rank` + `qk_rope_head_dim` values a row).

Device time: the summed durations of the trace's operations whose name
matches `op_regex`, or whose detail does and is a custom call (the decode
programs' calls: a window's call of the indexer has another name; an
operation that takes the kernel's result names it in its detail and is not
counted). The least time: calls x max(FLOPs/peak,
bytes/peak). Contexts are the client's view, as trace_op_time.py takes them.
Nothing without a device trace, where no such operation ran in the slice (a
program without the kernel: the parent's, or another configuration's), or for
a configuration without an indexer."""

import importlib.util
import os
import re

import kernels
import kernels_dsa


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
    if not model.get("index_topk"):
        return None
    pattern = re.compile(spec["op_regex"])
    calls, seconds = 0, 0.0
    for trace in ctx.traces:
        for dev in trace.get("devices", []):
            for name, row in dev["ops"].items():
                # The kernel's own rows: its name, or a custom call that
                # names it. (A fusion that CONSUMES the kernel's output names
                # it among its operands too, and is not a call of it.)
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
    if spec["cost"] == "indexer":
        cost = kernels_dsa.indexer_decode(
            context_tokens, lanes, model["index_n_heads"],
            model["index_head_dim"])
    else:
        cost = kernels_dsa.selected_attention_decode(
            context_tokens, lanes, model["index_topk"],
            model["num_attention_heads"],
            model["kv_lora_rank"] + model["qk_rope_head_dim"],
            model["kv_lora_rank"])
    least, bound = kernels.roofline_seconds(cost, ctx.device_kind)
    ctx.notes[f"dsa_{spec['cost']}_decode"] = {
        "calls": calls, "kernel_seconds": seconds, "bound": bound,
        "mean_context_tokens_per_call": context_tokens,
        "mean_lanes": lanes, "least_seconds_per_call": least}
    return 100.0 * calls * least / seconds
