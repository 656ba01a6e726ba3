"""A percentile of readings taken inside the run (client-side latencies).

`q` in 0..100, by linear interpolation over all readings of the window;
an earlier line of the run states how many there were.
"""


def reduce(ctx, samples, q, scale=1.0):
    values = ctx.samples.get(samples)
    if not values:
        return None
    import numpy as np

    return scale * float(np.percentile(np.asarray(values, np.float64), q))
