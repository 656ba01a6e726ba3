"""Model FLOP/s utilization of serving, in percent: the FLOPs that the
prompt tokens prefilled and the tokens decoded in the window NEED, over
the chip's bf16 peak times the window.

Needed (lib/laguna.py, at the configuration's share): every useful token
through the attention projections, the router, the shared expert and
layer 0's dense MLP; every decoded token and every admitted prompt's last
position through the head; the routed experts' three matmuls for the
assignments that fell on experts held, scaled by useful over computed
tokens (the device counts the rows of bucket padding and idle slots too);
QK^T and PV over the K/V rows each query attends, from the batcher's
`attended` counters.  The program's counters are absent on a program
without them: nothing to read."""
import json

from lib import laguna

PREFILL = "serving.batcher.prefill.tokens"
PADDED = "serving.batcher.prefill.padded_tokens"
FILL = "hist.serving.batcher.batch_fill.sum"
TICKS = "hist.serving.batcher.batch_fill.count"
ASSIGNED = "serving.moe.assignments"
ADMITTED = "hist.serving.batcher.queue_wait.count"


def reduce(ctx, seconds="window_s", slots="max_slots"):
    c = ctx.counters
    if not c.get(seconds) or ASSIGNED not in c or FILL not in c:
        return None
    decoded = c[FILL] * ctx.params[slots]
    useful = decoded + c.get(PREFILL, 0.0)
    computed = c[TICKS] * ctx.params[slots] + c.get(PADDED, 0.0)
    if computed <= 0:
        return None
    parts = {
        "tokens": laguna.token_flops(ctx.config) * useful,
        "head": laguna.head_flops(ctx.config) * (decoded
                                                 + c.get(ADMITTED, 0.0)),
        "experts": (laguna.expert_flops_per_assignment(ctx.config)
                    * c[ASSIGNED] * useful / computed),
        "attention": laguna.attention_flops(
            ctx.config, c.get("serving.batcher.attended.full", 0.0),
            c.get("serving.batcher.attended.window", 0.0)),
    }
    print(json.dumps({"line": "serve_mfu", "flops": parts,
                      "useful_tokens": useful,
                      "computed_tokens": computed}), flush=True)
    return 100.0 * sum(parts.values()) / (ctx.chips * ctx.peaks["flops"]
                                          * c[seconds])
