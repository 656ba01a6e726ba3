"""lib/loadgen.py's clients and window arithmetic, with the requests' lengths
drawn in strata: the same two lognormals, and every seed the same work.

Run as `python benchmarks/lib/loadgen_strata.py` with loadgen.py's spec on
stdin; its result on stdout.  The traffic file names the draw:
`"lengths": {"draw": "strata", "block": B}`.

Where a window closes a few dozen requests whose cost hangs on a heavy
tail (a prompt past 4,096 tokens is admitted by a program of 8,192), the
independent draws of loadgen.py make the seed decide how much work a run
does.  Here the requests are numbered in the order they are SENT, whoever
sends them, and each run of B consecutive requests holds one prompt length
from each B-th of the prompts' lognormal and one reply length from each
B-th of the replies', uniform inside its stratum (so the lengths are still
draws from the file's distributions, clipped to its limits), in two orders
the seed shuffles apart.  The k-th request sent, ids and lengths, depends
on (seed, k) alone; which client sends it follows from the serving.
"""
from __future__ import annotations

import io
import json
import os
import sys
import threading
from statistics import NormalDist

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import loadgen  # noqa: E402


def stratum_length(u: float, median: float, sigma: float, lo: int,
                   hi: int) -> int:
    """The lognormal's length at quantile u, rounded and clipped as
    `loadgen.lognormal_length` rounds and clips a draw."""
    z = NormalDist().inv_cdf(min(max(u, 1e-12), 1.0 - 1e-12))
    return int(np.clip(round(median * float(np.exp(sigma * z))), lo, hi))


class Strata:
    """The stream of requests, in the order they are sent."""

    def __init__(self, seed: int, traffic: dict):
        self.seed, self.traffic = seed, traffic
        self.block = int(traffic["lengths"]["block"])
        self.lock = threading.Lock()
        self.sent = 0

    def lengths(self, b: int) -> list:
        """(prompt length, output length) of block b's requests."""
        rng = np.random.default_rng([self.seed, b, 0])
        out = []
        for d in (self.traffic["prompt_len"], self.traffic["output_len"]):
            u = (rng.permutation(self.block) + rng.random(self.block)) \
                / self.block
            out.append([stratum_length(x, d["median"], d["sigma"], d["min"],
                                       d["max"]) for x in u])
        return list(zip(*out))

    def request(self, k: int, vocab: int):
        """(prompt token ids, output length) of the k-th request sent."""
        n_prompt, n_out = self.lengths(k // self.block)[k % self.block]
        ids = np.random.default_rng([self.seed, k, 1])
        return ids.integers(0, vocab, size=n_prompt).tolist(), n_out

    def draw_request(self, rng, traffic: dict, vocab: int):
        """In `loadgen.draw_request`'s place: the client's own generator
        is not drawn from."""
        with self.lock:
            k = self.sent
            self.sent += 1
        return self.request(k, vocab)


def main() -> int:
    text = sys.stdin.read()
    spec = json.loads(text)
    # `Client.run` looks the name up in its own module; the rest of
    # loadgen.py (clients, records, window arithmetic) runs as it is
    loadgen.draw_request = Strata(spec["seed"], spec["traffic"]).draw_request
    sys.stdin = io.StringIO(text)
    return loadgen.main()


if __name__ == "__main__":
    sys.exit(main())
