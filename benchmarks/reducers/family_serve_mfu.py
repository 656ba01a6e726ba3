"""Model FLOP/s utilization of serving, in percent, by the arithmetic of
the cell's own configuration: the FLOPs that the prompt tokens prefilled
and the tokens decoded in the window NEED (`lib/<family>.py`
`useful_flops`), over the chip's bf16 peak times the window.

What the program counted over the window is handed over as it is: useful
tokens (live slots a tick, and the admissions' own prompt tokens), head
rows (one a decoded token and one an admitted prompt), the live
assignments on experts held (`serving.moe.live_assignments`: padding, idle
slots and zero-compute experts are not among them), and the positions
attended by kind of cache (`serving.batcher.attended.<kind>`, of which
`serving.batcher.prefill.attended.<kind>` by admissions).  A program
without the counters is nothing to read."""
import importlib
import json

PREFILL = "serving.batcher.prefill.tokens"
FILL = "hist.serving.batcher.batch_fill.sum"
LIVE = "serving.moe.live_assignments"
ADMITTED = "hist.serving.batcher.queue_wait.count"
ATTENDED = "serving.batcher.attended."
PREFILL_ATTENDED = "serving.batcher.prefill.attended."


def reduce(ctx, seconds="window_s", slots="max_slots"):
    c = ctx.counters
    if not c.get(seconds) or LIVE not in c or FILL not in c:
        return None
    decoded = c[FILL] * ctx.params[slots]
    family = importlib.import_module("lib." + ctx.config["family"])
    parts = family.useful_flops(ctx.config, {
        "useful": decoded + c.get(PREFILL, 0.0),
        "head_rows": decoded + c.get(ADMITTED, 0.0),
        "live_assignments": c[LIVE],
        "attended": {k[len(ATTENDED):]: v for k, v in c.items()
                     if k.startswith(ATTENDED)},
        "prefill_attended": {k[len(PREFILL_ATTENDED):]: v
                             for k, v in c.items()
                             if k.startswith(PREFILL_ATTENDED)}})
    print(json.dumps({"line": "family_serve_mfu", "flops": parts,
                      "useful_tokens": decoded + c.get(PREFILL, 0.0)}),
          flush=True)
    return 100.0 * sum(parts.values()) / (ctx.chips * ctx.peaks["flops"]
                                          * c[seconds])
