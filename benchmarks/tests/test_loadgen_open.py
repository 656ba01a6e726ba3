"""The open loop's schedule: gaps in strata, the same work for every seed,
requests due in order; and what it reports of a window."""
import json
import math
import os
import statistics
import types

import pytest

from lib import loadgen_open

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "open-poisson-chat.json")
SEEDS = [2**31 + 17 * i for i in range(20)]


def traffic():
    with open(TRAFFIC) as f:
        return json.load(f)


@pytest.mark.parametrize("block", [0, 1, 7, 40])
def test_a_block_holds_one_gap_from_each_stratum(block):
    t = traffic()
    rate, b = t["rate_per_s"], t["gaps"]["block"]
    edges = [-math.log1p(-i / b) for i in range(b)] + [math.inf]
    orders = set()
    for seed in SEEDS:
        gaps = loadgen_open.block_gaps(seed, rate, b, block)
        assert len(gaps) == b and min(gaps) > 0
        # the block lasts exactly b / rate whatever the seed
        assert sum(gaps) == pytest.approx(b / rate, rel=1e-12)
        # one scale puts the i-th smallest gap in the i-th stratum of the
        # unit exponential
        g = sorted(gaps)
        lo = max(edges[i] / g[i] for i in range(b))
        hi = min(edges[i + 1] / g[i] for i in range(b))
        assert lo <= hi
        orders.add(tuple(sorted(range(b), key=gaps.__getitem__)))
    assert len(orders) > len(SEEDS) // 2      # the seed shuffles the order
    assert loadgen_open.block_gaps(SEEDS[0], rate, b, block) == \
        loadgen_open.block_gaps(SEEDS[0], rate, b, block)


def test_every_seed_has_the_same_arrivals_in_each_block():
    t = traffic()
    rate, b = t["rate_per_s"], t["gaps"]["block"]
    for seed in SEEDS:
        due = loadgen_open.schedule(seed, t, 10.0, 40.0)
        assert due[0] == 10.0 and due == sorted(due) and due[-1] < 40.0
        # request k * b is due at k * b / rate: each block's interval
        # holds b arrivals for every seed
        for k in range(len(due) // b):
            assert due[k * b] == pytest.approx(10.0 + k * b / rate,
                                               abs=1e-9)


def test_every_seed_offers_a_window_the_same_work():
    t = traffic()
    rate, b = t["rate_per_s"], t["gaps"]["block"]
    window = (t["settle_s"], t["settle_s"] + 25.0)
    want = rate * 25.0
    counts = []
    for seed in SEEDS:
        due = loadgen_open.schedule(seed, t, 0.0, window[1])
        counts.append(sum(1 for d in due if window[0] <= d < window[1]))
    # only the two blocks the window's edges cut differ between seeds
    assert all(abs(c - want) <= b for c in counts)
    assert all(abs(c - want) / want < 0.02 for c in counts)
    q1, med, q3 = statistics.quantiles(counts, n=4)
    assert (q3 - q1) / med < 0.01


def test_requests_are_the_strata_s_in_due_order():
    t = traffic()
    spec = {"seed": 5, "traffic": t, "start_at": 100.0, "window_end": 101.0,
            "vocab": 50257}
    loop = loadgen_open.OpenLoop(spec)
    assert [loop.take() for _ in range(len(loop.due))] == list(
        range(len(loop.due)))
    assert loop.take() is None
    lens = [loop.strata.request(k, 50257) for k in range(16)]
    assert lens == [loop.strata.request(k, 50257) for k in range(16)]
    p, o = t["prompt_len"], t["output_len"]
    assert all(p["min"] <= len(ids) <= p["max"] and o["min"] <= n <= o["max"]
               for ids, n in lens)


def test_offered_counts_what_was_due_in_the_window():
    t = traffic()
    spec = {"window_start": 10.0, "window_end": 20.0, "traffic": t}
    loop = types.SimpleNamespace(
        due=[9.0, 10.0, 12.5, 19.9, 20.0],
        records=[{"t_send": 9.0, "t_sent": 9.5},
                 {"t_send": 10.0, "t_sent": 10.002},
                 {"t_send": 12.5, "t_sent": 12.5}])
    out = loadgen_open.offered(spec, loop)
    assert out["due_in_window"] == 3
    assert out["offered_per_s"] == pytest.approx(0.3)
    # lateness of the window's requests; the first one's apart
    assert out["late_ms"]["max"] == pytest.approx(2.0)
    assert out["first_late_ms"] == pytest.approx(500.0)
    assert (out["due_total"], out["sent_total"]) == (5, 3)
