"""What the path in front of the engine adds: the client's mean of a time
(from due) minus the engines' mean of their histogram over the same window.
Means, because only a histogram's sum and count are exact."""

from prom import delta_over
from stats import end_to_end


def read(spec, ctx):
    client = end_to_end(ctx.records, ctx.seconds, ctx.chips).get(spec["client"])
    total = delta_over(ctx.engine_scrapes, spec["histogram"] + "_sum")
    count = delta_over(ctx.engine_scrapes, spec["histogram"] + "_count")
    if client is None or total is None or not count:
        return None
    return client - spec.get("scale", 1.0) * total / count
