"""The Mamba-1 layers' decode kernel's share of its roofline (%):
trace_ssm_op_time.py's quantity for a kernel whose shapes come from the jamba
family's keys of the configuration (`mamba_expand` x `hidden_size` channels,
`mamba_d_state` state values a channel) and whose counts are
kernels_ssm1.py's.

Device time: the summed durations of the trace's operations whose name or
detail matches `op_regex`. The least time: calls x max(FLOPs/peak,
bytes/peak). A call moves the state of every sequence of its step; the lanes
are the client's view, as trace_op_time.py takes them (that module's
`contexts_in_slice`, loaded from its file): the requests then between their
first and last token, so a padding lane is not counted. Nothing without a
device trace, where no such operation ran in the slice (a program without the
kernel), or for a configuration without such layers."""

import importlib.util
import os
import re

import kernels
import kernels_ssm1


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
    if not model.get("mamba_dt_rank"):
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
    _, lanes = _contexts_in_slice()(ctx.records, ctx.trace_span)
    replicas = max(len(ctx.traces), 1)
    cost = kernels_ssm1.ssm1_state_update(
        lanes / replicas, model["mamba_expand"] * model["hidden_size"],
        model["mamba_d_state"])
    least, bound = kernels.roofline_seconds(cost, ctx.device_kind)
    ctx.notes["ssm1_state_update"] = {
        "calls": calls, "kernel_seconds": seconds, "bound": bound,
        "mean_lanes": lanes / replicas, "least_seconds_per_call": least}
    return 100.0 * calls * least / seconds
