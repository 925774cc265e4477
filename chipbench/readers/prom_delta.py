"""delta of a counter over the window, summed over labels and processes."""

from prom import delta_over


def read(spec, ctx):
    return delta_over(ctx.scrapes(spec.get("target")), spec["counter"])
