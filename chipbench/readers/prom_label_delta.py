"""delta over the window of one series summed over CHOSEN values of one label
(and over the target's processes), times `scale`: {"name": ..., "label": ...,
"values": [...]}. None if the series is not exposed with any of them."""

from readers.prom_label_ratio import delta_of


def read(spec, ctx):
    moved = delta_of(ctx.scrapes(spec.get("target")), spec)
    return None if moved is None else spec.get("scale", 1.0) * moved
