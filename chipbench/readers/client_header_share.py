"""Of the window's turns after a session's first, the share (in %) served by
the replica that served the session's previous turn, by the gateway's
`x-gateway-destination-endpoint-served` header."""

from stats import measured


def read(spec, ctx):
    served = {(r.session, r.turn): r.served_by for r in ctx.records
              if r.session >= 0 and r.served_by}
    later = [r for r in measured(ctx.records, ctx.seconds)
             if r.session >= 0 and r.turn > 0 and r.served_by
             and (r.session, r.turn - 1) in served]
    if not later:
        return None
    same = sum(r.served_by == served[(r.session, r.turn - 1)] for r in later)
    return 100.0 * same / len(later)
