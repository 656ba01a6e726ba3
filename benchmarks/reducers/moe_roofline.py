"""The grouped expert matmul's share of its roofline, in percent: the
least time the chip could take for the (token, expert) assignments and
the touched experts of the traced slice (lib/laguna.moe_work: the larger
of three matmuls an assignment over peak FLOP/s, and every touched
expert's weights once plus every assignment's row in and out over peak
bytes/s) over the device time the kernel's operations took there.  A lower
bound on the work whatever implements it, so consistent counters cannot
read over 100%.  `assignments` and `touched` name counters the driver cut
to the slice; without them, or without the kernel in the trace, there is
nothing to read."""
import json

from lib import laguna
from lib import trace as tr


def reduce(ctx, pattern, assignments, touched):
    if ctx.trace is None or not ctx.slice.get(assignments):
        return None
    seconds = tr.op_seconds(ctx.trace, pattern)
    measured = sum(seconds.values()) / max(len(seconds), 1)
    if measured <= 0:
        return None
    need = laguna.moe_work(ctx.config, ctx.slice[assignments],
                           ctx.slice.get(touched, 0.0))
    by_flops = need["flops"] / ctx.peaks["flops"]
    by_bytes = need["bytes"] / ctx.peaks["hbm_bytes"]
    print(json.dumps({"line": "moe_roofline",
                      "bound": "flops" if by_flops >= by_bytes else "bytes",
                      "assignments": ctx.slice[assignments],
                      "touched": ctx.slice.get(touched, 0.0),
                      "least_s": max(by_flops, by_bytes),
                      "measured_s": measured}), flush=True)
    return 100.0 * max(by_flops, by_bytes) / measured
