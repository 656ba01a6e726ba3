"""Laguna-S-2.1 served over loopback HTTP, at one chip's share of a
two-chip layer: the `MoELM` of the configuration file behind the same
`ContinuousBatcher` and streaming endpoint as `drivers/lm_serve.py`,
whose `measure` and `close` this driver uses as they are.

    read_stream().continuous_server(...).parse_request(schema=[...])
        .stream_reply(fn).options(stream_workers=clients).start()

Set-up makes the weights in bf16 on the device leaf by leaf
(lib/laguna.py), then forms every admission shape the traffic can form
(prompt buckets x padded rows, at most `context` prompt tokens a program)
until a whole pass compiles nothing.

`verify` judges WHAT IS SERVED.  The loop is stopped and the sampled
requests are replayed through the batcher itself (`teacher_force`: its
host path, its page tables and ring, its admission and decode functions
at the cell's slots and shapes, each step fed the token that was served),
with the programs handing back their logits and, per routed layer, the
layer's input, router logits, chosen experts and routed sum.  Then the
pools are freed and lib/reference_laguna.py (float32, `highest`) is run
over prompt and reply: end to end, by its own routing except where the
served set lies within `route_deficit`'s limit of a tie; and layer by
layer, on the served layer's own input.  `LIMITS` says what each reading
is held to.  `LAGUNA_CONTROL=<fault>` in the environment serves with a
deliberate fault (lib/laguna_controls.py): the run must come out not
correct.
"""
from __future__ import annotations

import copy
import os
import sys

from drivers.lm_serve import _buckets, close, measure as _measure

# What `verify` holds a run to: each reading at or under its limit.  The
# served model computes in bf16 (f32 accumulation, f32 router and softmax
# statistics) over a bf16 cache; the reference in f32 on the same bf16
# weights.  Every reading comes from the tokens that were served or from
# the served functions replayed at the cell's shapes.  A limit lies
# between what sound runs read and what a control reads: the chip
# readings of both are in PERF.md, section 6, PR 26 (second round).
#
# End to end (the reference over prompt and reply):
#   p99_margin    how far under the reference's best logit the served
#                 tokens lie, at the 99th percentile of about a thousand.
#                 The worst one (`max_diff`) is printed: the replay flips
#                 an expert at a tie that the window did not in a token
#                 or two of a thousand, and one such token read 0.295 in
#                 a sound run (0.044 and 0.065 in the two others).
#                 Sound 0.0077-0.0107; control `window_short` 0.056.
#   logit_rms     RMS of (the replayed programs' logits - the
#                 reference's) over the replies' rows, steady to a
#                 percent.  Sound 0.0084-0.0085; `window_short` 0.026.
#   route_deficit router-logit units by which the worst served expert of
#                 any (token, layer) lies under the reference's own k-th.
#                 A bf16 residual stream moves router logits by a few
#                 hundredths, so near ties fall the other way: within the
#                 limit the reference computes that token with the served
#                 set, beyond it with its own, and the run fails.  Sound
#                 0.027-0.043; `window_short` 0.117.
# Layer by layer (the equations on the served layer's own input, so that
# upstream rounding is out of the way and f32 against bf16 shows):
#   router_err    largest |served router logit - the equations'| on the
#                 input the router read (`router_input`: XLA hands the
#                 router the norm's float32 from before its rounding to
#                 bf16, and the tap hands out the same).  Sound 0.0, bit
#                 for bit; control `router_bf16` 0.0154.
#   route_miss    how far under the equations' k-th logit the worst
#                 served expert lies: the top-k itself.  Sound 0.0;
#                 `router_bf16` 0.0121.
#   route_differ  share of (token, layer) sets that are not the top-k of
#                 the equations' router logits, the larger of the
#                 admissions' rows and the decode steps'.  Sound 0.0;
#                 `router_bf16` 0.042 of the decode steps' rows.
#   expert_err    relative RMS error of the routed experts' weighted sum,
#                 the larger of the two kinds of rows.  bf16 inputs and
#                 products are exact in f32; what is left is the rounding
#                 of the gated activation and of the output to bf16.
#                 Sound 0.00334, steady to a tenth of a percent; control
#                 `experts_bf16` (an accumulator in bf16) 0.0102.
# Printed beside them and limiting nothing: `mean_margin` (a mean that
# one flipped token doubles), `max_diff`, `routing_differ_share` (end to
# end; 0.067-0.078 in sound runs and under every control alike),
# `replay_agree_share`.
LIMITS = {"p99_margin": 0.025, "logit_rms": 0.015, "route_deficit": 0.1,
          "router_err": 1e-3, "route_miss": 1e-3, "route_differ": 0.02,
          "expert_err": 0.006}

# the counters the traced slice is cut with (reducers read their deltas)
SLICE_COUNTERS = ("serving.moe.assignments", "serving.moe.experts_touched",
                  "serving.batcher.pages.full", "serving.batcher.pages.window",
                  "serving.batcher.prefill.tokens",
                  "serving.batcher.prefill.attended.full",
                  "serving.batcher.prefill.attended.window")


class _SliceUnits:
    """The harness's slice with the program's counters added to the units
    the driver counts across it."""

    def __init__(self, inner):
        self._inner = inner

    def open_window(self, units):
        from mmlspark_tpu.core import telemetry

        def both():
            counted = telemetry.counters()
            return {**units(),
                    **{k: float(counted.get(k, 0)) for k in SLICE_COUNTERS}}

        self._inner.open_window(both)

    def poll(self):
        self._inner.poll()

    def close(self):
        self._inner.close()


def _warm(env, batcher, meter_count) -> dict:
    """Every admission shape: each prompt bucket at each padded row count
    whose rows x bucket stay within the context (the most prompt tokens
    the batcher puts into one admission program).  A wave's
    submits are made under a long interpreter switch interval, so the
    loop thread admits them as ONE prefill.  A pass must compile
    nothing before set-up is over."""
    import numpy as np

    p = env.traffic["prompt_len"]
    slots, cap = env.params["max_slots"], env.config["context"]
    rng = np.random.default_rng(env.seed)
    waves = [(b, k) for b in _buckets(p["min"], p["max"])
             for k in (2 ** i for i in range(slots.bit_length()))
             if k <= slots and k * b <= max(cap, b)]
    passes = []
    old = sys.getswitchinterval()
    for _ in range(4):
        before = meter_count()
        for bucket, k in waves:
            n = min(bucket, p["max"])
            prompts = [rng.integers(0, env.config["vocab_size"],
                                    size=n).tolist() for _ in range(k)]
            sys.setswitchinterval(5.0)
            try:
                streams = [batcher.submit(q, max_new_tokens=2)
                           for q in prompts]
            finally:
                sys.setswitchinterval(old)
            for s in streams:
                s.tokens()
        passes.append(meter_count() - before)
        if len(passes) > 1 and passes[-1] == 0:
            break
    else:
        raise RuntimeError(f"warm-up never settled: compiles per pass "
                           f"{passes}")
    return {"waves": len(waves), "compiles_per_pass": passes}


def setup(env) -> dict:
    from lib import laguna, laguna_controls
    from mmlspark_tpu.serving import read_stream
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    control = os.environ.get("LAGUNA_CONTROL")
    if control:
        laguna_controls.arm(control)
        env.log({"line": "control", "armed": control})
    model = laguna.build(env.config, env.config["context"])
    variables = laguna.init_on_device(model, env.seed)
    batcher = ContinuousBatcher(
        model, variables, max_slots=env.params["max_slots"], paged=True,
        page_size=env.params["page_size"])

    def fn(row):
        for tok in batcher.submit([int(t) for t in row["prompt"]],
                                  int(row["max_new_tokens"])):
            yield f"{tok} "

    query = (read_stream()
             .continuous_server(name="bench-laguna-serve", path="/generate")
             .parse_request(schema=["prompt", "max_new_tokens"])
             .stream_reply(fn)
             .options(stream_workers=env.traffic["clients"])
             .start())
    batcher.start()
    st = {"model": model, "variables": variables, "batcher": batcher,
          "query": query}
    try:
        env.log({"line": "warmup",
                 **_warm(env, batcher, lambda: env.meter.count)})
    except BaseException:
        close(st)
        raise
    return st


def measure(env, st) -> dict:
    counted = copy.copy(env)
    counted.slice = _SliceUnits(env.slice)
    return _measure(counted, st)


def verify(env, st, measured) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib import reference_laguna as ref

    sample = st["sample"]
    if not sample:
        return {"correct": False, "why": "no completed request to compare"}
    params = st["variables"]["params"]
    arch = ref.arch_of(env.config)
    sparse = [i for i in range(arch["layers"]) if arch["sparse"][i]]
    step = 16 if env.rehearse else 512
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    width = min(-(-longest // step) * step, env.config["context"])
    replies = max(len(r["tokens"]) for r in sample)

    # what is served, replayed: the loop has to be dead first
    st.pop("query").stop()
    st["batcher"].stop()
    served = st["batcher"].teacher_force(
        [(r["prompt"], r["tokens"]) for r in sample])
    # the batcher holds the page pools, and the reference wants f32
    # blocks of its own: free the one before building the other
    close(st)

    def reference(params, toks, rows, taps):
        chosen = taps["experts"].astype(jnp.int32)
        h, own, deficits = ref.hidden(params, toks, arch, chosen=chosen,
                                      band=LIMITS["route_deficit"])
        checks = [ref.layer_check(params[f"layer{i}"]["moe"],
                                  {tap: v[j] for tap, v in taps.items()},
                                  arch) for j, i in enumerate(sparse)]
        return (ref.logits_at(params, h, rows), jnp.stack(own),
                jnp.stack(deficits),
                {k: jnp.stack([c[k] for c in checks]) for k in checks[0]})

    reference = jax.jit(reference)
    margins, sq, agree = [], [], []
    deficit, differ, compared = 0.0, 0, 0
    router_err, route_miss = 0.0, 0.0
    # layer by layer, the admissions' rows and the decode steps' apart
    differing, rows_seen = np.zeros(2), np.zeros(2)
    routed_sq, routed_ref_sq = np.zeros(2), np.zeros(2)
    for r, got in zip(sample, served):
        n, m = len(r["prompt"]), len(r["tokens"])
        fed = n + m - 1                  # positions the programs were fed
        seq = r["prompt"] + r["tokens"]
        toks = np.zeros(width, np.int32)
        toks[:len(seq)] = seq            # causal: the padding is unseen
        rows = np.zeros(replies, np.int32)
        rows[:m] = n - 1 + np.arange(m)
        taps = {}
        for tap, v in got["routing"].items():
            taps[tap] = np.zeros((v.shape[0], width, v.shape[2]), v.dtype)
            taps[tap][:, :fed] = v
        lg_r, own, short, check = jax.tree.map(np.asarray, reference(
            params, jnp.asarray(toks), jnp.asarray(rows),
            jax.tree.map(jnp.asarray, taps)))
        lg_p, lg_r = got["logits"], lg_r[:m]
        tok = np.asarray(r["tokens"])
        margins.append(lg_r.max(-1) - lg_r[np.arange(m), tok])
        sq.append(np.square(lg_p - lg_r).mean(-1))
        agree.append(lg_p.argmax(-1) == tok)
        got_e = np.sort(taps["experts"][:, :fed].astype(np.int64), -1)
        differ += int((got_e != np.sort(own[:, :fed], -1)).any(-1).sum())
        compared += got_e.shape[0] * fed
        deficit = max(deficit, float(short[:, :fed].max()))
        router_err = max(router_err, float(check["router_err"][:, :fed].max()))
        route_miss = max(route_miss, float(check["route_miss"][:, :fed].max()))
        for k, part in enumerate((slice(0, n), slice(n, fed))):
            differing[k] += check["differs"][:, part].sum()
            rows_seen[k] += check["differs"][:, part].size
            routed_sq[k] += check["routed_sq"][:, part].sum()
            routed_ref_sq[k] += check["routed_ref_sq"][:, part].sum()
    margin = np.concatenate(margins)
    expert_err = np.sqrt(routed_sq / np.maximum(routed_ref_sq, 1e-30))
    differ_by_part = differing / np.maximum(rows_seen, 1)
    readings = {"p99_margin": float(np.percentile(margin, 99)),
                "logit_rms": float(np.sqrt(np.concatenate(sq).mean())),
                "route_deficit": deficit,
                "router_err": router_err, "route_miss": route_miss,
                "route_differ": float(differ_by_part.max()),
                "expert_err": float(expert_err.max())}
    over = [k for k, limit in LIMITS.items() if not readings[k] <= limit]
    return {"correct": bool(not over and measured["failed"] == 0
                            and measured["attempted"] > 0),
            "compared": "the served tokens and the served programs "
                        "replayed at the cell's shapes (logits, routing, "
                        "routed sums) vs the plain f32 reference, end to "
                        "end and layer by layer",
            **readings, "tol": LIMITS, "over": over,
            "control": os.environ.get("LAGUNA_CONTROL"),
            "requests": len(sample), "tokens": int(len(margin)),
            "max_diff": float(margin.max()),
            "mean_margin": float(margin.mean()),
            "exact_argmax_share": float((margin == 0).mean()),
            "replay_agree_share": float(np.concatenate(agree).mean()),
            "routing_differ_share": differ / max(compared, 1),
            "routings_compared": compared,
            "expert_err_prefill": float(expert_err[0]),
            "expert_err_decode": float(expert_err[1]),
            "route_differ_prefill": float(differ_by_part[0]),
            "route_differ_decode": float(differ_by_part[1])}
