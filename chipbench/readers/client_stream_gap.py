"""The longest stop of one stream as the client saw it: over the records, the
longest interval between consecutive `pieces` of one request whose later
piece arrived inside the measured window, in ms. The witness from outside
that the engine's and the gateway's own longest periods are read against;
the notes name the request, the moment, and how late the generator itself
ran (a client that stopped shows there too). None without two pieces."""

from stats import generator_report


def read(spec, ctx):
    worst = None
    for r in ctx.records:
        for (t0, _), (t1, _) in zip(r.pieces, r.pieces[1:]):
            if 0.0 <= t1 <= ctx.seconds and (worst is None or t1 - t0 > worst[0]):
                worst = (t1 - t0, r.rid, t0)
    if worst is None:
        return None
    gap, rid, at = worst
    # A --trace 2 run reads this over the window and over its traced tail:
    # a note each, told apart by their length.
    ctx.notes[f"longest stream gap at the client in {ctx.seconds:g} s"] = {
        "rid": rid, "from_s": at, "to_s": at + gap,
        "late_max_ms": generator_report(ctx.records, ctx.seconds)["late_max_ms"]}
    return gap * 1e3
