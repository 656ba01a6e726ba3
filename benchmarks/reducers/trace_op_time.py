"""Device time of the operations whose name or detail matches `pattern`
(and not `exclude`), summed over the traced slice, averaged over the
devices, in milliseconds per `per` (a unit the driver counts across the
slice: `steps`, `ticks`, `images`), times `per_scale` (1000 for "per
thousand images")."""
from lib import trace as tr


def reduce(ctx, pattern, per, exclude=None, per_scale=1.0):
    if ctx.trace is None or not ctx.slice.get(per):
        return None
    seconds = tr.op_seconds(ctx.trace, pattern, exclude)
    mean = sum(seconds.values()) / len(seconds)
    return 1e3 * mean * per_scale / ctx.slice[per]
