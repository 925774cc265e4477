"""Mean of a gauge over the 5 Hz samples taken inside the window, over all
replicas, times `scale`."""

from prom import total


def read(spec, ctx):
    values = [v for _, per_replica in ctx.gauge_samples
              for v in (total(s, spec["gauge"]) for s in per_replica)
              if v is not None]
    if not values:
        return None
    return spec.get("scale", 1.0) * sum(values) / len(values)
