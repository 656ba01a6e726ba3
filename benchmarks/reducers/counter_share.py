"""scale * num / (num + the sum of `others`) over the window's counters:
one counter's share of a whole that several counters make up.  A `num`
that is absent (a program without the counter), or a whole of zero, is
nothing to read."""


def reduce(ctx, num, others, scale=100.0):
    if num not in ctx.counters:
        return None
    whole = ctx.counters[num] + sum(ctx.counters.get(o, 0.0) for o in others)
    if whole <= 0:
        return None
    return scale * ctx.counters[num] / whole
