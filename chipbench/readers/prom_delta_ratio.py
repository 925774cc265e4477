"""delta(numerator) / delta(denominator) over the window, summed over the
target's processes, times `scale`. None if the denominator did not move."""

from prom import delta_over


def read(spec, ctx):
    scrapes = ctx.scrapes(spec.get("target"))
    num = delta_over(scrapes, spec["numerator"])
    den = delta_over(scrapes, spec["denominator"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den
