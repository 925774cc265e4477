"""A statistic of the client's own records over the window's requests, by its
name in stats.end_to_end (for one that is too unsteady to carry a bound and
stands among the per-layer metrics instead)."""

from stats import end_to_end


def read(spec, ctx):
    return end_to_end(ctx.records, ctx.seconds, ctx.chips).get(spec["stat"])
