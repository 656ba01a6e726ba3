"""A decode tick's share of its roofline, in percent: the least time the
chip could take for what one tick NEEDS over the time the program's own
tick timer gives it.

What a tick needs: every matmul weight read once and the live K and V
rows of every layer (`lib/flops.decode_tick_bytes` at the mean live tokens
a tick, from the batcher's `live_tokens` counter), or the matmuls' FLOPs
for one token a slot, whichever takes longer at the chip's peaks.  The
time: `seconds` over `ticks`, the sum and the count of the batcher's tick
histogram, which leaves admissions out.  An earlier line says which of the
two bounds it, and carries counts only.
"""
import json

from lib import flops


def reduce(ctx, ticks, seconds, live, rows="max_slots"):
    c = ctx.counters
    if not c.get(ticks) or not c.get(seconds) or live not in c:
        return None
    live_tokens = c[live] / c[ticks]
    need_bytes = flops.decode_tick_bytes(ctx.config, live_tokens)
    need_flops = (2.0 * flops.lm_param_counts(ctx.config)["matmul"]
                  * ctx.params[rows])
    by_bytes = need_bytes / ctx.peaks["hbm_bytes"]
    by_flops = need_flops / ctx.peaks["flops"]
    print(json.dumps({"line": "decode_roofline",
                      "bound": "flops" if by_flops >= by_bytes else "bytes",
                      "live_tokens_a_tick": live_tokens,
                      "bytes_a_tick": need_bytes, "flops_a_tick": need_flops,
                      "ticks": c[ticks]}), flush=True)
    return 100.0 * max(by_bytes, by_flops) * c[ticks] / c[seconds]
