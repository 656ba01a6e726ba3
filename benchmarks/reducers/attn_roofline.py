"""The admission flash forward's share of its roofline, in percent: the
least time the chip could take for the prompts admitted in the traced
slice (lib/laguna.prefill_attention_work: QK^T and PV over the (query,
key) pairs each prompt's own positions attend in every layer, over peak
FLOP/s; or q, k, v read and the output written once, over peak bytes/s)
over the device time of the kernel there.  `tokens`, `full` and `window`
name the batcher's admission counters as the driver cut them to the slice:
prompt tokens, and pairs attended in ONE layer of each kind.  Bucket
padding is computed by the kernel and is not in the counters, so
consistent counters cannot read over 100%."""
import json

from lib import laguna
from lib import trace as tr


def reduce(ctx, pattern, tokens, full, window):
    if ctx.trace is None or not ctx.slice.get(full):
        return None
    seconds = tr.op_seconds(ctx.trace, pattern)
    measured = sum(seconds.values()) / max(len(seconds), 1)
    if measured <= 0:
        return None
    need = laguna.prefill_attention_work(
        ctx.config, ctx.slice.get(tokens, 0.0), ctx.slice[full],
        ctx.slice.get(window, 0.0))
    by_flops = need["flops"] / ctx.peaks["flops"]
    by_bytes = need["bytes"] / ctx.peaks["hbm_bytes"]
    print(json.dumps({"line": "attn_roofline",
                      "bound": "flops" if by_flops >= by_bytes else "bytes",
                      "least_s": max(by_flops, by_bytes),
                      "measured_s": measured}), flush=True)
    return 100.0 * max(by_flops, by_bytes) / measured
