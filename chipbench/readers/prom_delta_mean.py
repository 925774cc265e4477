"""Mean of a histogram over the window: delta(<name>_sum) / delta(<name>_count),
summed over the target's processes, times `scale`. The buckets are too coarse
for a quantile, so none is read."""

from prom import delta_over


def read(spec, ctx):
    scrapes = ctx.scrapes(spec.get("target"))
    total = delta_over(scrapes, spec["histogram"] + "_sum")
    count = delta_over(scrapes, spec["histogram"] + "_count")
    if total is None or not count:
        return None
    return spec.get("scale", 1.0) * total / count
