"""delta(numerator) / delta(denominator) over the window, times `scale`,
where each side is one series summed over CHOSEN values of one label (and
over the target's processes): {"name": ..., "label": ..., "values": [...]}.
None if a side is not exposed or the denominator did not move."""


def delta_of(scrapes, side):
    wanted = [f'{side["label"]}="{value}"' for value in side["values"]]
    parts = [after[key] - before.get(key, 0.0)
             for before, after in scrapes for key in after
             if key[0] == side["name"] and any(w in key[1] for w in wanted)]
    return sum(parts) if parts else None


def read(spec, ctx):
    scrapes = ctx.scrapes(spec.get("target"))
    num = delta_of(scrapes, spec["numerator"])
    den = delta_of(scrapes, spec["denominator"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den
