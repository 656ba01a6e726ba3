"""Model FLOP/s utilization of training, in percent, by the arithmetic of
the cell's own configuration: the FLOPs that the tokens trained in the
window NEED (`lib/<family>.py` `train_flops`: forward and backward, causal
attention once, nothing made twice), over chips times the chip's bf16 peak
times the window.

What the program counted over the window is handed over as it is: the
assignments that fell on experts held (`training.moe.assignments`), the
causal pairs attended (`training.attn.pairs`), the MTP head's targets
(`training.mtp.tokens`).  A program without the counters is nothing to
read."""
import importlib
import json

ASSIGNED = "training.moe.assignments"
PAIRS = "training.attn.pairs"
MTP = "training.mtp.tokens"


def reduce(ctx, tokens="tokens", seconds="window_s"):
    c = ctx.counters
    if not c.get(seconds) or not c.get(tokens) or not c.get(PAIRS):
        return None
    family = importlib.import_module("lib." + ctx.config["family"])
    parts = family.train_flops(ctx.config, {
        "tokens": c[tokens], "assignments": c.get(ASSIGNED, 0.0),
        "pairs": c[PAIRS], "mtp_tokens": c.get(MTP, 0.0)})
    print(json.dumps({"line": "family_train_mfu", "flops": parts,
                      "flops_per_token": sum(parts.values()) / c[tokens]}),
          flush=True)
    return 100.0 * sum(parts.values()) / (ctx.chips * ctx.peaks["flops"]
                                          * c[seconds])
