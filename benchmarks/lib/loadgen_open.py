"""The open loop: requests due at Poisson times at a fixed rate, sent over
loopback HTTP by a pool of sender threads, in a process of its own that
never imports JAX (the parent holds the chip).

Run as `python benchmarks/lib/loadgen_open.py` with lib/loadgen.py's spec on
stdin; one JSON result on stdout.  The traffic file gives the rate
(`rate_per_s`), the sender threads (`clients`), the gaps and the lengths
drawn in strata (`gaps`, `lengths`) and the settle before the window.

An arrival does not wait for any reply.  The k-th request is due `start_at`
plus the first k gaps; every block of B consecutive gaps holds one gap from
each B-th of the exponential, uniform inside it, in an order the seed
shuffles, and is scaled to last exactly B / rate.  Request k * B is then
due at `start_at + k * B / rate` whatever the seed, and a window's count
differs between seeds only inside the two blocks its edges cut (over some
570 arrivals an independent Poisson draw moves the offered load by about
5%).  The k-th request's ids and lengths are `loadgen_strata.Strata`'s
k-th.  A sender takes the next request, sleeps until it is due and sends it.
A request counts from when it was DUE: it belongs to the window it was due
in, and its time to first token includes any wait for a sender, which is
reported beside (`late_ms`, due to sent over the window's requests;
`first_late_ms`, the first request's, is how long this process took to
start).  Records and window arithmetic are lib/loadgen.py's.
"""
from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import loadgen, loadgen_strata  # noqa: E402


def block_gaps(seed: int, rate: float, block: int, b: int) -> list:
    """Block b's gaps in seconds: one from each `block`-th of the unit
    exponential, in an order the seed shuffles, scaled so that the block
    lasts exactly `block / rate` (the top stratum is unbounded, so an
    unscaled block would keep a whole exponential's variance)."""
    rng = np.random.default_rng([seed, b, 2])
    u = (rng.permutation(block) + rng.random(block)) / block
    gaps = -np.log1p(-u)
    return (gaps * (block / rate / gaps.sum())).tolist()


def schedule(seed: int, traffic: dict, start_at: float, until: float) -> list:
    """Due times of the requests from `start_at`, the last before `until`."""
    rate, block = float(traffic["rate_per_s"]), int(traffic["gaps"]["block"])
    due, t, b = [], start_at, 0
    while True:
        for gap in block_gaps(seed, rate, block, b):
            if t >= until:
                return due
            due.append(t)
            t += gap
        b += 1


def send(spec: dict, rec: dict, body: bytes) -> None:
    """One request, its reply read chunk by chunk as it streams (the
    server writes one chunk per token, "<id> ")."""
    try:
        conn = http.client.HTTPConnection(spec["host"], spec["port"],
                                          timeout=spec["timeout_s"])
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
    rec["t_end"] = time.monotonic()


class OpenLoop:
    """The due requests and the senders that take them in order."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.strata = loadgen_strata.Strata(spec["seed"], spec["traffic"])
        self.due = schedule(spec["seed"], spec["traffic"], spec["start_at"],
                            spec["window_end"])
        self.lock = threading.Lock()
        self.taken = 0
        self.records = []

    def take(self):
        with self.lock:
            k = self.taken
            if k >= len(self.due):
                return None
            self.taken += 1
            return k

    def sender(self) -> None:
        spec = self.spec
        while (k := self.take()) is not None:
            prompt, n_out = self.strata.request(k, spec["vocab"])
            body = json.dumps({"prompt": prompt,
                               "max_new_tokens": n_out}).encode()
            time.sleep(max(0.0, self.due[k] - time.monotonic()))
            # `t_send` is when it was due: loadgen.summarize's window and
            # time to first token count from there
            rec = {"prompt": prompt, "want": n_out, "arrivals": [],
                   "counts": [], "tokens": [], "error": None,
                   "t_send": self.due[k], "t_sent": time.monotonic(),
                   "think_s": None}
            send(spec, rec, body)
            self.records.append(rec)


def offered(spec: dict, loop: OpenLoop) -> dict:
    """What the loop offered the window, and how late its senders sent
    the window's requests (and the first one: how long the process took
    to start)."""
    ws, we = spec["window_start"], spec["window_end"]
    late = np.asarray([r["t_sent"] - r["t_send"] for r in loop.records
                       if ws <= r["t_send"] < we])
    first = min(loop.records, key=lambda r: r["t_send"], default=None)
    n = sum(1 for t in loop.due if ws <= t < we)
    return {"due_in_window": n, "offered_per_s": n / (we - ws),
            "rate_per_s": float(spec["traffic"]["rate_per_s"]),
            "late_ms": {"p99": float(1e3 * np.percentile(late, 99)),
                        "max": float(1e3 * late.max())} if late.size else None,
            "first_late_ms": None if first is None
            else 1e3 * (first["t_sent"] - first["t_send"]),
            "due_total": len(loop.due), "sent_total": len(loop.records)}


def main() -> int:
    spec = json.load(sys.stdin)
    loop = OpenLoop(spec)
    threads = [threading.Thread(target=loop.sender, daemon=True,
                                name=f"loadgen-sender-{i}")
               for i in range(spec["traffic"]["clients"])]
    for t in threads:
        t.start()
    deadline = spec["window_end"] + spec["timeout_s"] + 5.0
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    alive = [t.name for t in threads if t.is_alive()]
    out = loadgen.summarize(spec, list(loop.records))
    out.update(offered(spec, loop))
    out["hung_clients"] = alive
    out["failed"] += len(alive)
    out["attempted"] += len(alive)
    json.dump(out, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
