"""Model FLOP/s utilization of training, in percent: tokens a second
times the FLOPs a token NEEDS (lib/flops.py: forward and backward, causal
attention once, no recompute) over chips times the chip's bf16 peak."""
from lib import flops


def reduce(ctx, tokens="tokens", seconds="window_s"):
    if not ctx.counters.get(seconds) or tokens not in ctx.counters:
        return None
    per_token = flops.lm_train_flops_per_token(ctx.config,
                                               ctx.traffic["seq_len"])
    rate = ctx.counters[tokens] / ctx.counters[seconds]
    return 100.0 * rate * per_token / (ctx.chips * ctx.peaks["flops"])
