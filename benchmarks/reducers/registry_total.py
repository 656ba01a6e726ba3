"""scale * the summed `sum` of named histograms of the program's registry,
as they stand when the run is reduced: the PROCESS totals.

The counter table holds the window's deltas, and what set-up spent is all
before the window opens: there the delta is zero and the total is the
number.  `names` are histogram names (every label set of a name counts).
A program that records none of them, as one from before the names existed,
is nothing to read.
"""


def reduce(ctx, names, scale=1.0):
    from mmlspark_tpu.core import telemetry

    total, found = 0.0, False
    for (name, _labels), hist in telemetry.REGISTRY.histograms().items():
        if name in names:
            snap = hist.snapshot()
            if snap["count"]:
                found = True
                total += float(snap["sum"])
    return scale * total if found else None
