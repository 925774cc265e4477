"""The highest bucket of a histogram that filled in the window: over the
target's (before, after) scrapes, the upper bound of the highest
`<histogram>_bucket{le=...}` whose OWN count (its cumulative delta less that
of the next lower bucket) rose, times `scale`. Quantised on purpose: it says
how long the longest observation was to within a bucket, which a mean over
hundreds of observations cannot. `+Inf` reads as the last finite bound and the
notes say so; 0.0 where the histogram is exposed and nothing was observed;
None only where it is not exposed."""

import re

_LE = re.compile(r'le="([^"]+)"')


def read(spec, ctx):
    name = spec["histogram"] + "_bucket"
    rose: dict[float, float] = {}
    for before, after in ctx.scrapes(spec.get("target")):
        for key, value in after.items():
            if key[0] == name:
                le = float(_LE.search(key[1]).group(1))
                rose[le] = rose.get(le, 0.0) + value - before.get(key, 0.0)
    if not rose:
        return None
    bounds = sorted(rose)
    top, lower = 0.0, 0.0
    for bound in bounds:
        if rose[bound] - lower > 0:
            top = bound
        lower = rose[bound]
    if top == float("inf"):
        top = bounds[-2] if len(bounds) > 1 else 0.0
        ctx.notes[f"top bucket of {spec['histogram']} in {ctx.seconds:g} s"] = (
            f"an observation beyond the last finite bound {top}: read as it")
    return spec.get("scale", 1.0) * top
