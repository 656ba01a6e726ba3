"""The paged decode attention's share of its roofline, in percent: the K
and V bytes of the pages in use in the traced slice (`full` and `window`
name the batcher's page counters as the driver cut them to the slice: pages
in use summed a tick, a layer of each kind; lib/laguna.py multiplies by
the layers of the kind, the page and the row) over peak bytes/s, over the
device time of the page walks there.  The walk is bound by bytes: its
FLOPs are a few per byte."""
import json

from lib import laguna
from lib import trace as tr


def reduce(ctx, pattern, full, window):
    if ctx.trace is None or not ctx.slice.get(full):
        return None
    seconds = tr.op_seconds(ctx.trace, pattern)
    measured = sum(seconds.values()) / max(len(seconds), 1)
    if measured <= 0:
        return None
    need = laguna.paged_attention_bytes(
        ctx.config, ctx.slice[full], ctx.slice.get(window, 0.0),
        ctx.params["page_size"])
    least = need / ctx.peaks["hbm_bytes"]
    print(json.dumps({"line": "kv_roofline", "bytes": need,
                      "least_s": least, "measured_s": measured}),
          flush=True)
    return 100.0 * least / measured
