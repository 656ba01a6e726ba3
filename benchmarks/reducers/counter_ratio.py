"""scale * num / den over the window's counters.

`num` and `den` name entries of the flat counter table (the program's
telemetry deltas over the window, plus what the driver and the harness
count: `images`, `tokens`, `steps`, `window_s`, `setup_s`, `compile_s`).
Without `den` the value is scale * num.  A counter that is absent, or a
zero denominator, is nothing to read.
"""


def reduce(ctx, num, den=None, scale=1.0):
    if num not in ctx.counters:
        return None
    value = ctx.counters[num]
    if den is not None:
        if not ctx.counters.get(den):
            return None
        value /= ctx.counters[den]
    return scale * value
