"""Share of the traced slice that the lowest-numbered device spends in
collectives while nothing else runs on it, in percent: the most that
overlapping them with compute could save."""
from lib import trace as tr


def reduce(ctx, pattern=tr.COLLECTIVE):
    if ctx.trace is None or not ctx.slice.get("seconds"):
        return None
    events = ctx.trace.devices[min(ctx.trace.devices)]
    _all, exposed = tr.exposed_seconds(events, pattern)
    return 100.0 * exposed / (ctx.slice["t1"] - ctx.slice["t0"])
