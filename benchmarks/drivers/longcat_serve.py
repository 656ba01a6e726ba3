"""LongCat-Flash-Chat served over loopback HTTP, at one chip's share of
a 32-chip layer: the `LongCatLM` of the configuration file behind the same
`ContinuousBatcher` and streaming endpoint as `drivers/lm_serve.py` and
`drivers/laguna_serve.py`, whose `close` and warm-up this driver uses as
they are.  `measure` is `lm_serve.measure` line for line but for the
generator it starts: lib/loadgen_strata.py, which draws the traffic
file's lengths in strata so that every seed offers the same work (a
window closes some sixty requests here, and a prompt past 4,096 tokens
costs six 2,048-token ones).

Set-up makes the weights in bf16 on the device leaf by leaf
(lib/longcat.py), then forms every admission shape the traffic can form
(prompt buckets x padded rows, at most `context` prompt tokens a program)
until a whole pass compiles nothing.

`verify` judges WHAT IS SERVED, as `laguna_serve.verify` does.  The loop
is stopped and the sampled requests are replayed through the batcher
itself (`teacher_force`: its host path, its latent page table, its
admission and decode functions at the cell's slots and shapes, each step
fed the token that was served), with the programs handing back their
logits, per routed layer the layer's input, router logits, chosen experts
and routed sum, and per latent attention of a decode step the absorbed
query as the page walk read it.  Then the pools are freed and
lib/reference_longcat.py (float32, `highest`, attention expanded) is run
over prompt and reply: end to end, by its own routing except where the
served set lies within `route_deficit`'s limit of a tie; and layer by
layer, on the served layer's own input.  `LIMITS` says what each reading
is held to.  `LONGCAT_CONTROL=<fault>` in the environment serves with a
deliberate fault (lib/longcat_controls.py): the run must come out not
correct.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from drivers.laguna_serve import _warm
from drivers.lm_serve import _ticks, close

# What `verify` holds a run to: each reading at or under its limit.  The
# served model computes in bf16 (f32 accumulation, f32 router, softmax
# statistics and absorbed query) over a bf16 latent cache; the reference
# in f32 on the same bf16 weights, attention expanded.  Every reading
# comes from the tokens that were served or from the served functions
# replayed at the cell's shapes.  A limit lies between what sound runs
# read and what a control reads, with room on both sides: the chip
# readings below (nine sound runs, seven at pools of 2,049 pages and two
# at dense parity; one or two runs a control, those of the review round
# at dense parity under these limits; my chip runs, PR 31) are tabled
# in PERF.md, section 6, PR 31.
#
# End to end (the reference over prompt and reply):
#   p99_margin    how far under the reference's best logit the served
#                 tokens lie, at the 99th percentile of three to four
#                 thousand (the worst one, `max_diff`, is printed: an
#                 expert flipped at a tie moves a token or two; 0.025-
#                 0.037 in sound runs).  Sound 0.0043-0.0076; control
#                 `kv_scale_off` 0.0252, 0.0278.
#   logit_rms     RMS of (the replayed programs' logits - the
#                 reference's) over the replies' rows, steady to half a
#                 percent.  Sound 0.00830-0.00833; `kv_scale_off` 0.0215,
#                 0.0231 (a decode step's rows enter the cache 3.46
#                 times too small).
#   route_deficit how far the worst served expert of any (token, block)
#                 lies under the reference's own twelfth biased score, in
#                 units of that twelfth's probability (a relative
#                 distance, as a distance of router logits is).  A bf16
#                 residual stream moves scores by a few hundredths of
#                 themselves, so near ties fall the other way (8.4% of
#                 (token, block) sets differ end to end): within the
#                 limit the reference computes that token with the served
#                 set, beyond it with its own, and the run fails.  Sound
#                 0.032-0.042; `kv_scale_off` 0.127, 0.154.
# Layer by layer (the equations on the served layer's own input, so that
# upstream rounding is out of the way and f32 against bf16 shows):
#   router_err    largest |served router logit - the equations'| on the
#                 input the router read.  Sound 0.0, bit for bit.
#   route_miss    how far under the equations' twelfth biased score the
#                 worst served expert lies: the top-k with its selection
#                 bias.  Sound 0.0.
#   route_differ  share of (token, block) sets that are not the top-k of
#                 the equations' biased scores, the larger of the
#                 admissions' rows and the decode steps'.  Sound 0.0.
#                 (No control of this cell moves these three; the router
#                 is `moe_lm._router_logits`, whose bf16 control in
#                 `laguna-serve-mixed` read 0.0155, 0.0121 and 0.042.)
#   expert_err_prefill, expert_err_decode
#                 relative RMS error of the routed sum (FFN experts held
#                 and identity experts), over the admissions' rows and
#                 over the decode steps' rows, each held to its own limit:
#                 the two are different programs (the 128-row and the
#                 16-row tile of the grouped matmul, the chunked and the
#                 whole dispatch) and read differently when sound.
#                 Admissions: sound 0.00034-0.00041; control
#                 `prefill_experts_bf16` (a bf16 accumulator in the
#                 admissions' tiles) 0.00107.  Decode steps: sound
#                 0.00016-0.00019; `experts_bf16` (the same in the decode
#                 step's tile) 0.00103; `zero_off`, which leaves the
#                 identity experts' part, nearly all of this chip's sum,
#                 out of the decode steps' rows, 0.997.
#   ffn_err_prefill, ffn_err_decode
#                 the same of the FFN experts' part alone (the served sum
#                 less the equations' identity part): what the grouped
#                 matmul computes, a thirtieth of the sum.  bf16 inputs
#                 and products are exact in f32; what is left is the
#                 rounding of the gated activation and of the output to
#                 bf16 (`laguna-serve-mixed`, the same kernel at narrower
#                 experts, reads 0.0033).  Admissions: sound 0.0047-
#                 0.0054; `prefill_experts_bf16` 0.0142.  Decode steps:
#                 sound 0.00235; `experts_bf16` 0.0136.  A bf16
#                 accumulator adds 0.013 beside what a sound layer reads,
#                 2.6 times the admissions' own: a limit between the two
#                 has a half more room on either side, no more.
#   zero_share_diff  |share of served assignments on identity experts -
#                 the share in the equations' own sets|: the same router
#                 must send the same share (0.330-0.335) to experts that
#                 cost nothing.  Sound 0.0.
#   absorb_err    largest |the absorbed query the page walk multiplied -
#                 q_nope . Wkvb_K in float32|, over the RMS of the
#                 latter, on decode rows (the first 8 heads of every
#                 latent attention).  The walk reads the query as a bf16
#                 pair hi + lo, 16 bits of mantissa.  Sound 3.9e-5 to
#                 5.9e-5; control `absorb_bf16` (hi alone) 0.0205, 0.0384,
#                 which NO other reading sees (its `logit_rms` 0.00830).
# Printed beside them and limiting nothing: `mean_margin`, `max_diff`,
# `routing_differ_share` (end to end), `replay_agree_share` (0.983-0.991:
# replayed alone, an admission's near ties fall otherwise than in the
# served batch), `route_differ`'s two kinds of rows apart, `zero_share`.
LIMITS = {"p99_margin": 0.015, "logit_rms": 0.013, "route_deficit": 0.07,
          "router_err": 1e-3, "route_miss": 1e-3, "route_differ": 0.02,
          "expert_err_prefill": 0.00065, "expert_err_decode": 0.00045,
          "ffn_err_prefill": 0.008, "ffn_err_decode": 0.0056,
          "zero_share_diff": 0.002, "absorb_err": 1e-3}

# the counters the traced slice is cut with (reducers read their deltas)
SLICE_COUNTERS = ("serving.moe.assignments", "serving.moe.experts_touched",
                  "serving.batcher.pages.latent",
                  "serving.batcher.attended.latent",
                  "serving.batcher.prefill.tokens",
                  "serving.batcher.prefill.attended.latent")


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


def setup(env) -> dict:
    from lib import longcat, longcat_controls

    # a program without this model (the parent of the PR that added it)
    # fails here, at once: before any weight is made
    model = longcat.build(env.config, env.config["context"])

    from mmlspark_tpu.serving import read_stream
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    control = os.environ.get("LONGCAT_CONTROL")
    if control:
        longcat_controls.arm(control)
        env.log({"line": "control", "armed": control})
    variables = longcat.init_on_device(model, env.seed)
    batcher = ContinuousBatcher(
        model, variables, max_slots=env.params["max_slots"], paged=True,
        page_size=env.params["page_size"],
        num_pages=env.params.get("num_pages"))

    def fn(row):
        for tok in batcher.submit([int(t) for t in row["prompt"]],
                                  int(row["max_new_tokens"])):
            yield f"{tok} "

    query = (read_stream()
             .continuous_server(name="bench-longcat-serve", path="/generate")
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
    import jax

    info = st["query"].service_info
    traffic = env.traffic
    now = time.monotonic()
    start_at = now + env.params["loadgen_start_s"]
    window_start = start_at + traffic["ramp_s"] + traffic["settle_s"]
    spec = {"host": info.host, "port": info.port, "path": info.path,
            "seed": env.seed, "traffic": traffic,
            "vocab": env.config["vocab_size"], "start_at": start_at,
            "window_start": window_start,
            "window_end": window_start + env.seconds,
            "timeout_s": env.params["request_timeout_s"],
            "sample": env.params["verify_requests"]}
    loadgen = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "lib", "loadgen_strata.py")
    child = subprocess.Popen([sys.executable, loadgen],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
    units = _SliceUnits(env.slice)
    try:
        child.stdin.write(json.dumps(spec).encode())
        child.stdin.close()
        # ramp and settle are set-up: the window opens when they are over
        time.sleep(max(0.0, window_start - time.monotonic()))
        ticks0 = _ticks()
        units.open_window(lambda: {"ticks": _ticks()})
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.monotonic() < spec["window_end"]:
                units.poll()
                time.sleep(0.02)
        units.close()
        ticks = _ticks() - ticks0
        out = json.loads(child.stdout.read())
        child.wait(timeout=spec["timeout_s"] + 30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    st["sample"] = out.pop("sample")
    ttft, itl = out.pop("ttft_ms"), out.pop("itl_ms")
    return {"attempted": out["attempted"], "failed": out["failed"],
            "counters": {"tokens": float(out["tokens_in_window"]),
                         "window_s": float(env.seconds),
                         "ticks": float(ticks),
                         "requests": float(out["attempted"])},
            "samples": {"ttft_ms": ttft, "itl_ms": itl},
            "notes": out}


def verify(env, st, measured) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib import reference_longcat as ref

    sample = st["sample"]
    if not sample:
        return {"correct": False, "why": "no completed request to compare"}
    params = st["variables"]["params"]
    arch = ref.arch_of(env.config)
    blocks = arch["layers"]
    step = 16 if env.rehearse else 512
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    width = min(-(-longest // step) * step, env.config["context"])
    replies = max(len(r["tokens"]) for r in sample)
    # `input` (what the experts read) is `router_input` at the model's
    # dtype, and is made so below: its tap is left on the device, where
    # an 8,192-token replay's taps take 2 GB beside weights and pools
    moe_taps = ("router_input", "logits", "experts", "routed")
    mla_taps = ("mla_query", "mla_q_nope")

    # what is served, replayed: the loop has to be dead first
    st.pop("query").stop()
    st["batcher"].stop()
    served = st["batcher"].teacher_force(
        [(r["prompt"], r["tokens"]) for r in sample],
        taps=moe_taps + mla_taps)
    # the batcher holds the page pools, and the reference wants f32
    # blocks of its own: free the one before building the other
    close(st)

    def reference(params, toks, rows, taps, absorbed):
        chosen = taps["experts"].astype(jnp.int32)
        h, own, deficits = ref.hidden(params, toks, arch, chosen=chosen,
                                      band=LIMITS["route_deficit"])
        checks = [ref.layer_check(params[f"layer{i}"]["moe"],
                                  {tap: v[i] for tap, v in taps.items()},
                                  arch) for i in range(blocks)]
        absorb = [ref.absorb_check(
            params[f"layer{j // 2}"][f"attn{j % 2}"],
            {tap: v[j] for tap, v in absorbed.items()}, arch)
            for j in range(2 * blocks)]
        return (ref.logits_at(params, h, rows), jnp.stack(own),
                jnp.stack(deficits),
                {k: jnp.stack([c[k] for c in checks]) for k in checks[0]},
                jnp.stack(absorb))

    reference = jax.jit(reference)
    margins, sq, agree = [], [], []
    deficit, differ, compared = 0.0, 0, 0
    router_err, route_miss, absorb_err = 0.0, 0.0, 0.0
    zero, zero_ref, assigned = 0, 0, 0
    # layer by layer, the admissions' rows and the decode steps' apart
    differing, rows_seen = np.zeros(2), np.zeros(2)
    routed_sq, routed_ref_sq = np.zeros(2), np.zeros(2)
    ffn_sq, ffn_ref_sq = np.zeros(2), np.zeros(2)
    for r, got in zip(sample, served):
        n, m = len(r["prompt"]), len(r["tokens"])
        fed = n + m - 1                  # positions the programs were fed
        seq = r["prompt"] + r["tokens"]
        toks = np.zeros(width, np.int32)
        toks[:len(seq)] = seq            # causal: the padding is unseen
        rows = np.zeros(replies, np.int32)
        rows[:m] = n - 1 + np.arange(m)
        taps, absorbed = {}, {}
        for tap in moe_taps:
            v = got["routing"][tap]
            taps[tap] = np.zeros((v.shape[0], width, v.shape[2]), v.dtype)
            taps[tap][:, :fed] = v
        taps["input"] = taps["router_input"].astype(
            jnp.dtype(env.config["dtype"]))
        for tap in mla_taps:             # the decode steps' rows
            v = got["routing"].get(tap)
            if v is None:                          # a one-token reply
                v = np.zeros((2 * blocks, 0, 1), np.float32)
            absorbed[tap] = np.zeros((v.shape[0], replies, v.shape[2]),
                                     v.dtype)
            absorbed[tap][:, :m - 1] = v
        lg_r, own, short, check, absorb = jax.tree.map(np.asarray, reference(
            params, jnp.asarray(toks), jnp.asarray(rows),
            jax.tree.map(jnp.asarray, taps),
            jax.tree.map(jnp.asarray, absorbed)))
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
        if m > 1:
            absorb_err = max(absorb_err, float(absorb[:, :m - 1].max()))
        zero += int(check["zero"][:, :fed].sum())
        zero_ref += int(check["zero_ref"][:, :fed].sum())
        assigned += got_e.size
        for k, part in enumerate((slice(0, n), slice(n, fed))):
            differing[k] += check["differs"][:, part].sum()
            rows_seen[k] += check["differs"][:, part].size
            routed_sq[k] += check["routed_sq"][:, part].sum()
            routed_ref_sq[k] += check["routed_ref_sq"][:, part].sum()
            ffn_sq[k] += check["ffn_sq"][:, part].sum()
            ffn_ref_sq[k] += check["ffn_ref_sq"][:, part].sum()
    margin = np.concatenate(margins)
    expert_err = np.sqrt(routed_sq / np.maximum(routed_ref_sq, 1e-30))
    ffn_err = np.sqrt(ffn_sq / np.maximum(ffn_ref_sq, 1e-30))
    differ_by_part = differing / np.maximum(rows_seen, 1)
    readings = {"p99_margin": float(np.percentile(margin, 99)),
                "logit_rms": float(np.sqrt(np.concatenate(sq).mean())),
                "route_deficit": deficit,
                "router_err": router_err, "route_miss": route_miss,
                "route_differ": float(differ_by_part.max()),
                "expert_err_prefill": float(expert_err[0]),
                "expert_err_decode": float(expert_err[1]),
                "ffn_err_prefill": float(ffn_err[0]),
                "ffn_err_decode": float(ffn_err[1]),
                "zero_share_diff": abs(zero - zero_ref) / max(assigned, 1),
                "absorb_err": absorb_err}
    over = [k for k, limit in LIMITS.items() if not readings[k] <= limit]
    return {"correct": bool(not over and measured["failed"] == 0
                            and measured["attempted"] > 0),
            "compared": "the served tokens and the served programs "
                        "replayed at the cell's shapes (logits, routing, "
                        "routed sums, absorbed queries) vs the plain f32 "
                        "reference with attention expanded, end to end "
                        "and layer by layer",
            **readings, "tol": LIMITS, "over": over,
            "control": os.environ.get("LONGCAT_CONTROL"),
            "requests": len(sample), "tokens": int(len(margin)),
            "max_diff": float(margin.max()),
            "mean_margin": float(margin.mean()),
            "exact_argmax_share": float((margin == 0).mean()),
            "replay_agree_share": float(np.concatenate(agree).mean()),
            "routing_differ_share": differ / max(compared, 1),
            "routings_compared": compared,
            "zero_share": zero / max(assigned, 1),
            "route_differ_prefill": float(differ_by_part[0]),
            "route_differ_decode": float(differ_by_part[1])}
