"""Device idle share (%): 1 - busy/window of the traced slice, where busy is
the union of the intervals in which an operation ran, averaged over the
traced chips. Nothing without a device trace."""


def read(spec, ctx):
    devices = [d for t in ctx.traces for d in t.get("devices", [])]
    shares = [1.0 - d["busy_s"] / d["window_s"] for d in devices
              if d["window_s"] > 0]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
