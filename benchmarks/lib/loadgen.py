"""The load generator: closed-loop clients over loopback HTTP, in a process
of its own that never imports JAX (the parent holds the chip).

Run as `python benchmarks/lib/loadgen.py` with one JSON spec on stdin; one
JSON result on stdout.  Everything about the traffic is in the spec's
`traffic` (a file under benchmarks/traffic/): how many clients, the
lognormal prompt and output lengths, the ramp.  Client c's requests depend
on (seed, c) alone.  Times are `time.monotonic()`, which parent and child
share on Linux, so the parent fixes the window's edges and the child
counts what fell inside them.

Each client sends its next request when the last chunk of the previous
reply has arrived, and reads the chunked reply as it streams (the server
writes one chunk per token, "<id> ").
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time

import numpy as np


def lognormal_length(rng, median: float, sigma: float, lo: int, hi: int) -> int:
    return int(np.clip(round(float(rng.lognormal(np.log(median), sigma))),
                       lo, hi))


def draw_request(rng, traffic: dict, vocab: int):
    """(prompt token ids, output length) of one request."""
    p, o = traffic["prompt_len"], traffic["output_len"]
    n_prompt = lognormal_length(rng, p["median"], p["sigma"], p["min"],
                                p["max"])
    n_out = lognormal_length(rng, o["median"], o["sigma"], o["min"], o["max"])
    return rng.integers(0, vocab, size=n_prompt).tolist(), n_out


def client_rng(seed: int, client: int):
    return np.random.default_rng([seed, client])


class Client(threading.Thread):
    def __init__(self, spec: dict, index: int):
        super().__init__(name=f"loadgen-client-{index}", daemon=True)
        self.spec = spec
        self.index = index
        self.rng = client_rng(spec["seed"], index)
        self.records = []

    def run(self):
        spec = self.spec
        traffic = spec["traffic"]
        start = spec["start_at"] + (traffic.get("ramp_s", 0.0) * self.index
                                    / traffic["clients"])
        time.sleep(max(0.0, start - time.monotonic()))
        last_end = None
        while time.monotonic() < spec["window_end"]:
            prompt, n_out = draw_request(self.rng, traffic, spec["vocab"])
            rec = {"prompt": prompt, "want": n_out, "arrivals": [],
                   "counts": [], "tokens": [], "error": None}
            body = json.dumps({"prompt": prompt,
                               "max_new_tokens": n_out}).encode()
            rec["t_send"] = time.monotonic()
            rec["think_s"] = (None if last_end is None
                              else rec["t_send"] - last_end)
            try:
                conn = http.client.HTTPConnection(
                    spec["host"], spec["port"], timeout=spec["timeout_s"])
                try:
                    conn.request("POST", spec["path"], body=body)
                    resp = conn.getresponse()
                    if resp.status != 200:
                        raise RuntimeError(f"HTTP {resp.status}: "
                                           f"{resp.read()[:200]!r}")
                    text = b""
                    while True:
                        data = resp.read1(65536)
                        if not data:
                            break
                        rec["arrivals"].append(time.monotonic())
                        rec["counts"].append(data.count(b" "))
                        text += data
                    rec["tokens"] = [int(t) for t in text.split()]
                finally:
                    conn.close()
            except Exception as e:  # noqa: BLE001 — a failed request is a count
                rec["error"] = repr(e)[:300]
            last_end = rec["t_end"] = time.monotonic()
            self.records.append(rec)


def summarize(spec: dict, records: list) -> dict:
    """Everything the window's edges decide, counted here."""
    ws, we = spec["window_start"], spec["window_end"]
    timeout_ms = 1e3 * spec["timeout_s"]
    started = [r for r in records if ws <= r["t_send"] < we]
    failed = [r for r in started
              if r["error"] or len(r["tokens"]) != r["want"]]
    ttft, itl, tokens_in_window = [], [], 0
    for r in records:
        for t, n in zip(r["arrivals"], r["counts"]):
            if ws <= t < we:
                tokens_in_window += n
        for a, b in zip(r["arrivals"], r["arrivals"][1:]):
            if ws <= b < we:
                itl.append(1e3 * (b - a))
    for r in started:
        ok = r["arrivals"] and not r["error"]
        ttft.append(1e3 * (r["arrivals"][0] - r["t_send"]) if ok
                    else timeout_ms)
    think = [1e3 * r["think_s"] for r in started if r["think_s"] is not None]
    failed_ids = {id(r) for r in failed}
    good = [r for r in started if id(r) not in failed_ids]
    sample = [{"prompt": r["prompt"], "tokens": r["tokens"]}
              for r in good[:spec["sample"]]]

    def spread(values):
        if not values:
            return None
        v = np.asarray(values, np.float64)
        return {"n": len(values), "p50": float(np.percentile(v, 50)),
                "p95": float(np.percentile(v, 95)), "max": float(v.max())}

    return {
        "attempted": len(started), "failed": len(failed),
        "errors": [r["error"] for r in failed if r["error"]][:5],
        "tokens_in_window": tokens_in_window,
        "ttft_ms": ttft, "itl_ms": itl,
        "coalesced_reads": sum(1 for r in records for n in r["counts"]
                               if n > 1),
        "client_think_ms": spread(think),
        "prompt_lens": spread([len(r["prompt"]) for r in started]),
        "output_lens": spread([r["want"] for r in started]),
        "requests_total": len(records),
        "sample": sample,
    }


def main() -> int:
    spec = json.load(sys.stdin)
    clients = [Client(spec, i) for i in range(spec["traffic"]["clients"])]
    for c in clients:
        c.start()
    deadline = spec["window_end"] + spec["timeout_s"] + 5.0
    for c in clients:
        c.join(timeout=max(0.0, deadline - time.monotonic()))
    alive = [c.name for c in clients if c.is_alive()]
    records = [r for c in clients for r in list(c.records)]
    out = summarize(spec, records)
    out["hung_clients"] = alive
    out["failed"] += len(alive)
    out["attempted"] += len(alive)
    json.dump(out, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
