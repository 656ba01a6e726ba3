"""The two counters of the one-step-ahead decode loop as metrics: through
`counter_ratio` on counter tables written by hand, and, merged into a copy
of the benchmark (`with_shelved.py`), through `run.py`."""
import json
import os
import types

import pytest

from reducers import counter_ratio
import with_shelved
from test_run import BENCH, ROOT, run_py, timing_keys

SHELVED = os.path.join(BENCH, "metrics", "tick-overlap.shelved.json")
SERVING = ["lm-serve-closed", "laguna-serve-mixed"]
OVERLAPPED = "serving.batcher.tick.overlapped"
DISCARDS = "serving.batcher.tick.late_discards"
STEPS = "hist.serving.batcher.batch_fill.count"


def spec(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def reduced(name, counters):
    return counter_ratio.reduce(types.SimpleNamespace(counters=counters),
                                **spec(name)["args"])


def test_overlapped_frac_is_overlapped_steps_over_steps():
    assert reduced("tick_overlapped_frac",
                   {OVERLAPPED: 199.0, STEPS: 200.0}) == pytest.approx(99.5)
    # a loop that fetched every step before the next: the counter at 0
    assert reduced("tick_overlapped_frac",
                   {OVERLAPPED: 0.0, STEPS: 200.0}) == 0.0


def test_late_discards_is_the_counter():
    assert reduced("tick_late_discards", {DISCARDS: 0.0}) == 0.0
    assert reduced("tick_late_discards", {DISCARDS: 7.0}) == 7.0


@pytest.mark.parametrize("name,counters", [
    ("tick_overlapped_frac", {STEPS: 200.0}),      # the parent's program
    ("tick_overlapped_frac", {OVERLAPPED: 0.0, STEPS: 0.0}),   # no step
    ("tick_late_discards", {STEPS: 200.0})])
def test_nothing_to_read_is_left_out(name, counters):
    assert reduced(name, counters) is None


def test_the_entries_are_shelved_not_listed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {x["name"] for x in json.load(f)["per_layer"]}
    with open(SHELVED) as f:
        shelved = json.load(f)["per_layer"]
    assert [x["name"] for x in shelved] == ["tick_overlapped_frac",
                                            "tick_late_discards"]
    for x in shelved:
        assert x["name"] not in listed and x["workloads"] == SERVING
        mine = spec(x["name"])
        assert {k: mine[k] for k in x if k != "workloads"} == \
            {k: x[k] for k in x if k != "workloads"}


@pytest.fixture(scope="module")
def merged(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("merged"))
    with_shelved.merge(SHELVED, root)
    return root


def test_check_takes_the_merged_manifest(merged):
    done = run_py("--check", root=merged)
    assert done.returncode == 0, done.stdout
    assert json.loads(done.stdout.splitlines()[-1])["check"] == "ok"
    for cell in SERVING:
        with open(os.path.join(merged, "benchmarks", "workloads",
                               cell + ".json")) as f:
            assert {"tick_overlapped_frac", "tick_late_discards"} <= \
                set(json.load(f)["per_layer"])


@pytest.mark.parametrize("cell", SERVING)
def test_rehearse_reads_both_counters(merged, cell):
    """Closed-loop clients keep every slot live, so the loop is a step
    ahead nearly always; the traffic sends no eos_id."""
    done = run_py("--workload", cell, "--rehearse", "--trace", "1",
                  root=merged)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert {"tick_overlapped_frac", "tick_late_discards"} <= \
        set(last["reduced"])
    assert [k for x in lines if x.get("line") != "start"
            for k in timing_keys(x)] == []
