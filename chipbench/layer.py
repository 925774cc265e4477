"""Per-layer metrics: what a reader is given, and how a metric finds its
reader.

A per-layer metric is `chipbench/layer_metrics/<name>.json`: `{"kind": ...,
...inputs}`. `kind` names a module `chipbench/readers/<kind>.py` with one
function `read(spec, ctx) -> float | None`. A new counter needs a new JSON
file and no code; a new kind of reader is a new module found by that name. A
reader that finds nothing to read returns None and the metric is left out of
the line. Which cells report a metric, its layer and what it moves are
BENCHMARK.json's to say.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Context:
    records: list            # client.Record of the whole window phase
    seconds: float
    chips: int
    # (before, after) parsed /metrics around the window, per engine replica
    # and for the gateway.
    engine_scrapes: list
    gateway_scrape: tuple
    # [(seconds from window start, [parsed /metrics per replica])], 5 Hz.
    gauge_samples: list
    # trace_reduce output per replica (empty without --trace 1 or off a TPU)
    # and the slice of the window it covers, in seconds from its start.
    traces: list
    trace_span: tuple | None
    model: dict              # the configuration file
    device_kind: str
    # What a reader wants said beside its number (which bound, how many calls).
    notes: dict = dataclasses.field(default_factory=dict)

    def scrapes(self, target: str | None) -> list:
        """The (before, after) pairs of a metric's `target`: the engines
        (default) or the gateway."""
        return [self.gateway_scrape] if target == "gateway" else self.engine_scrapes


def metric_spec(name: str, base: str = HERE) -> dict:
    with open(os.path.join(base, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def reader(kind: str, base: str = HERE):
    path = os.path.join(base, "readers", f"{kind}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_reader_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metric(name: str, ctx: Context, base: str = HERE) -> float | None:
    spec = metric_spec(name, base)
    return reader(spec["kind"], base)(spec, ctx)
