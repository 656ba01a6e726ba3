"""`lm_serve`'s endpoint and batcher under an open loop: requests arrive at
the traffic file's rate whether or not earlier replies have come back
(lib/loadgen_open.py), so a burst queues in the batcher behind busy slots.

`setup` (bf16 weights, the paged batcher, `stream_workers` from the
traffic's `clients`, every admission shape warmed), `verify` and `close` are
lm_serve's own.  `measure` is lm_serve's but for the generator and the
window: there is no ramp, arrivals start at the rate and `settle_s` passes
before the window opens.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from drivers import lm_serve
from drivers.lm_serve import close, setup, verify  # noqa: F401


def _tail(values: list) -> dict | None:
    if not values:
        return None
    p50, p95, p99 = np.percentile(np.asarray(values, np.float64),
                                  [50, 95, 99])
    return {"p50": float(p50), "p95": float(p95), "p99": float(p99)}


def measure(env, st) -> dict:
    import jax

    info = st["query"].service_info
    traffic = env.traffic
    start_at = time.monotonic() + env.params["loadgen_start_s"]
    window_start = start_at + traffic["settle_s"]
    spec = {"host": info.host, "port": info.port, "path": info.path,
            "seed": env.seed, "traffic": traffic,
            "vocab": env.config["vocab_size"], "start_at": start_at,
            "window_start": window_start,
            "window_end": window_start + env.seconds,
            "timeout_s": env.params["request_timeout_s"],
            "sample": env.params["verify_requests"]}
    loadgen = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "lib", "loadgen_open.py")
    child = subprocess.Popen([sys.executable, loadgen],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        child.stdin.write(json.dumps(spec).encode())
        child.stdin.close()
        # the settle is set-up: the window opens when it is over
        time.sleep(max(0.0, window_start - time.monotonic()))
        ticks0 = lm_serve._ticks()
        env.slice.open_window(lambda: {"ticks": lm_serve._ticks()})
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.monotonic() < spec["window_end"]:
                env.slice.poll()
                time.sleep(0.02)
        env.slice.close()
        ticks = lm_serve._ticks() - ticks0
        out = json.loads(child.stdout.read())
        child.wait(timeout=spec["timeout_s"] + 30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    st["sample"] = out.pop("sample")
    ttft, itl = out.pop("ttft_ms"), out.pop("itl_ms")
    # the tails as the window saw them, in every run: a traced run's hold
    # the profiler's start and stop, during which arrivals go on and queue
    out["ttft_ms"], out["itl_ms"] = _tail(ttft), _tail(itl)
    return {"attempted": out["attempted"], "failed": out["failed"],
            "counters": {"tokens": float(out["tokens_in_window"]),
                         "window_s": float(env.seconds),
                         "ticks": float(ticks),
                         "requests": float(out["attempted"])},
            "samples": {"ttft_ms": ttft, "itl_ms": itl},
            "notes": out}
