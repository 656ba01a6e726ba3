"""Serving over loopback HTTP: a `ContinuousBatcher` the driver builds and
starts, behind the streaming endpoint, under the load generator.

    read_stream().continuous_server(...).parse_request(schema=[...])
        .stream_reply(fn).options(stream_workers=clients).start()

`fn` is `generate_stream`'s own closure with the request's
`max_new_tokens` handed to `submit` (the one-call endpoint fixes one
output length for everybody).  Weights are cast to bf16 once, the paged KV
cache is bf16, decoding is greedy.  The clients live in a child process
that never imports JAX (lib/loadgen.py).  The reference is the plain f32
forward of lib/reference.py over sampled requests' prompt and reply:
logits, not tokens, because random weights tie.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# A served (greedy) token must sit within this of the reference's best
# logit at its step.  The served model computes in bf16 over a bf16 cache,
# the reference in f32 on the same bf16-rounded weights: the worst margin
# over 1,085 served tokens on the chip was 0.031, with 97.7% of them the
# reference's own argmax (PR 22).  Five times that; an indexing fault
# (wrong page, wrong position) lands anywhere in a spread of several units.
MARGIN_TOL = 0.15

BATCH_FILL = "serving.batcher.batch_fill"


def _ticks() -> int:
    from mmlspark_tpu.core import telemetry

    return int(telemetry.histogram(BATCH_FILL).snapshot()["count"])


def _buckets(lo: int, hi: int) -> list:
    """The batcher's prompt buckets (powers of two from 16) that prompts
    of lo..hi tokens fall into."""
    out, b = [], 16
    while b < lo:
        b *= 2
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def _warm(env, batcher, meter_count) -> dict:
    """Declare every admission shape the traffic can form: each prompt
    bucket at each padded row count (powers of two up to the slots).  A
    wave's submits are made under a long interpreter switch interval, so
    that the batcher's loop thread sees all of them at its next drain and
    admits them as ONE prefill.  A second pass must compile nothing."""
    import numpy as np

    p = env.traffic["prompt_len"]
    slots = env.params["max_slots"]
    rows, r = [], 1
    while r <= slots:
        rows.append(r)
        r *= 2
    rng = np.random.default_rng(env.seed)
    waves = [(b, k) for b in _buckets(p["min"], p["max"]) for k in rows]
    passes = []
    old = sys.getswitchinterval()
    for _ in range(4):
        before = meter_count()
        for bucket, k in waves:
            prompts = [rng.integers(0, env.config["vocab_size"],
                                    size=bucket).tolist() for _ in range(k)]
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
    import jax
    import jax.numpy as jnp

    from lib.lm import build_lm
    from mmlspark_tpu.serving import read_stream
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    s, model = build_lm(env.config, env.config["n_positions"])
    # weights on the device from the seed, in the type they are served in,
    # in one jitted call
    variables = {"params": jax.jit(lambda r: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        model.init(r, jnp.zeros((1, 8), jnp.int32))["params"]))(
            jax.random.PRNGKey(env.seed))}
    batcher = ContinuousBatcher(model, variables,
                                max_slots=env.params["max_slots"], paged=True,
                                page_size=env.params["page_size"])

    def fn(row):
        for tok in batcher.submit([int(t) for t in row["prompt"]],
                                  int(row["max_new_tokens"])):
            yield f"{tok} "

    query = (read_stream()
             .continuous_server(name="bench-lm-serve", path="/generate")
             .parse_request(schema=["prompt", "max_new_tokens"])
             .stream_reply(fn)
             .options(stream_workers=env.traffic["clients"])
             .start())
    batcher.start()
    st = {"model": model, "variables": variables, "batcher": batcher,
          "query": query, "sizes": s}
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
        os.path.abspath(__file__))), "lib", "loadgen.py")
    child = subprocess.Popen([sys.executable, loadgen],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        child.stdin.write(json.dumps(spec).encode())
        child.stdin.close()
        # ramp and settle are set-up: the window opens when they are over
        time.sleep(max(0.0, window_start - time.monotonic()))
        ticks0 = _ticks()
        env.slice.open_window(lambda: {"ticks": _ticks()})
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.monotonic() < spec["window_end"]:
                env.slice.poll()
                time.sleep(0.02)
        env.slice.close()
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

    from lib.reference import lm_logits

    sample = st["sample"]
    if not sample:
        return {"correct": False, "why": "no completed request to compare"}
    s = st["sizes"]
    width = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    width = min(-(-width // 128) * 128, s["positions"])
    padded = np.zeros((len(sample), width), np.int32)
    at, tok = [], []
    for i, r in enumerate(sample):
        seq = r["prompt"] + r["tokens"]
        padded[i, :len(seq)] = seq
        for j, t in enumerate(r["tokens"]):
            at.append((i, len(r["prompt"]) + j - 1))
            tok.append(t)
    at, tok = np.asarray(at, np.int32), np.asarray(tok, np.int32)

    def margins(params, toks, at, tok):
        # padding sits after every position read, and attention is causal
        logits = lm_logits(params, toks, s["layers"], s["heads"])
        rows = logits[at[:, 0], at[:, 1]]
        return rows.max(-1) - jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]

    # the batcher holds 3 GB of pages and the reference wants f32 logits
    # of every sampled position: free the one before building the other
    close(st)
    m = np.asarray(jax.jit(margins)(st["variables"]["params"],
                                    jnp.asarray(padded), jnp.asarray(at),
                                    jnp.asarray(tok)))
    worst = float(m.max())
    return {"correct": bool(worst <= MARGIN_TOL and measured["failed"] == 0
                            and measured["attempted"] > 0),
            "compared": "each served token's logit vs the best logit of the "
                        "plain f32 forward at its step (worst margin)",
            "max_diff": worst, "tol": MARGIN_TOL, "requests": len(sample),
            "tokens": int(len(tok)),
            "exact_argmax_share": float((m == 0).mean())}


def close(st) -> None:
    import gc

    query = st.pop("query", None)
    batcher = st.pop("batcher", None)
    if query is not None:
        query.stop()
    if batcher is not None:
        batcher.stop()
    del query, batcher
    gc.collect()      # the page pools go with the batcher
