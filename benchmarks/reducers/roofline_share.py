"""A kernel's share of its roofline, in percent: the least time the chip
could take for what the ALGORITHM needs (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, both from lib/flops.py at the cell's
shapes) over the device time the kernel's operations took in the trace.
An earlier line says which of the two bounds it."""
import json

from lib import flops
from lib import trace as tr


def reduce(ctx, pattern, per, work, exclude=None):
    if ctx.trace is None or not ctx.slice.get(per):
        return None
    seconds = tr.op_seconds(ctx.trace, pattern, exclude)
    measured = sum(seconds.values()) / len(seconds) / ctx.slice[per]
    if measured <= 0:
        return None
    need = getattr(flops, work)(ctx.config, ctx.params["batch"],
                                ctx.traffic["seq_len"])
    by_flops = need["flops"] / ctx.peaks["flops"]
    by_bytes = need["bytes"] / ctx.peaks["hbm_bytes"]
    print(json.dumps({"line": "roofline", "work": work,
                      "bound": "flops" if by_flops >= by_bytes else "bytes",
                      "least_s": max(by_flops, by_bytes),
                      "measured_s": measured}), flush=True)
    return 100.0 * max(by_flops, by_bytes) / measured
