"""A kernel's share of its roofline, in percent, by the arithmetic of
the cell's own configuration: the least time the chip could take for what
the kernel's work in the traced slice NEEDS (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s) over the device time of the
operations matching `pattern` there.

The work comes from `lib/<family>.py`, the module the configuration file
names as its `family`: `work` names a function there, called as
`work(config, **counts, **sizes)` -> {"flops", "bytes"}.  `counts` maps
the function's arguments to counters the driver cut to the slice (one
that is absent counts 0), `sizes` maps arguments to the cell's `params`
(a page size).  `require` names the count without which there is nothing
to read: a program without the counter, as one from before it existed, or
a trace without the kernel, returns None.  A work function counts what
the algorithm needs whatever implements it (no padding, no idle slots),
so consistent counters cannot read over 100%.  The next configuration
brings a `lib/<family>.py` and metric files, not a reducer."""
import importlib
import json

from lib import trace as tr


def reduce(ctx, pattern, work, counts, require, sizes=None):
    if ctx.trace is None or not ctx.slice.get(counts[require]):
        return None
    seconds = tr.op_seconds(ctx.trace, pattern)
    measured = sum(seconds.values()) / max(len(seconds), 1)
    if measured <= 0:
        return None
    family = importlib.import_module("lib." + ctx.config["family"])
    counted = {arg: ctx.slice.get(name, 0.0) for arg, name in counts.items()}
    need = getattr(family, work)(
        ctx.config, **counted,
        **{arg: ctx.params[name] for arg, name in (sizes or {}).items()})
    by_flops = need["flops"] / ctx.peaks["flops"]
    by_bytes = need["bytes"] / ctx.peaks["hbm_bytes"]
    print(json.dumps({"line": "family_roofline", "work": work,
                      "bound": "flops" if by_flops >= by_bytes else "bytes",
                      **counted, "least_s": max(by_flops, by_bytes),
                      "measured_s": measured}), flush=True)
    return 100.0 * max(by_flops, by_bytes) / measured
