"""The load generator's requests repeat for a seed and differ across
seeds and clients, and its window arithmetic counts what it should."""
import json
import os

from lib import loadgen

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "closed-32-chat.json")


def traffic():
    with open(TRAFFIC) as f:
        return json.load(f)


def draws(seed, client, n=50):
    rng = loadgen.client_rng(seed, client)
    return [loadgen.draw_request(rng, traffic(), 50257) for _ in range(n)]


def test_lengths_repeat_for_a_seed():
    assert draws(7, 3) == draws(7, 3)


def test_lengths_differ_across_seeds_and_clients():
    assert draws(7, 3) != draws(8, 3)
    assert draws(7, 3) != draws(7, 4)


def test_lengths_within_the_file_s_limits():
    t = traffic()
    many = draws(1, 0, 2000)
    prompts = [len(p) for p, _ in many]
    outs = [n for _, n in many]
    assert min(prompts) >= t["prompt_len"]["min"]
    assert max(prompts) <= t["prompt_len"]["max"]
    assert min(outs) >= t["output_len"]["min"]
    assert max(outs) <= t["output_len"]["max"]
    prompts.sort()
    assert abs(prompts[1000] - t["prompt_len"]["median"]) < 20
    assert all(0 <= tok < 50257 for p, _ in many[:20] for tok in p)


def test_window_arithmetic():
    spec = {"window_start": 10.0, "window_end": 20.0, "timeout_s": 60.0,
            "sample": 1}
    ok = {"prompt": [1], "want": 3, "tokens": [5, 6, 7], "error": None,
          "t_send": 11.0, "think_s": 0.001, "t_end": 12.0,
          "arrivals": [11.5, 11.75, 12.0], "counts": [1, 1, 1]}
    early = dict(ok, t_send=9.0, arrivals=[9.5, 10.5, 10.75])
    short = dict(ok, t_send=15.0, tokens=[5], arrivals=[15.25], counts=[1])
    dead = dict(ok, t_send=16.0, tokens=[], arrivals=[], counts=[],
                error="timeout")
    out = loadgen.summarize(spec, [ok, early, short, dead])
    assert (out["attempted"], out["failed"]) == (3, 2)
    assert out["tokens_in_window"] == 3 + 2 + 1
    assert out["ttft_ms"] == [500.0, 250.0, 60000.0]
    assert sorted(out["itl_ms"]) == [250.0, 250.0, 250.0, 1000.0]
    assert out["sample"] == [{"prompt": [1], "tokens": [5, 6, 7]}]
