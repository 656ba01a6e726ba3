"""The six set-up metrics: the reducer on a registry primed by hand (and on
one from before the stage existed), the new open-loop cell that carries
them under `--rehearse`, and the shelved entries for the other cells
merged into a copy (`with_shelved.py`)."""
import json
import os
import types

import pytest

from reducers import setup_stage
import with_shelved
from test_run import BENCH, ROOT, cells, run_py, timing_keys

SIX = {"setup_start_s": "start", "setup_trace_lower_s": "trace_lower",
       "setup_compile_s": "compile", "setup_cache_unwritten": "unwritten",
       "setup_cache_hit_frac": "hit_frac", "setup_unclaimed_s": "unclaimed"}
SHELVED = os.path.join(BENCH, "metrics", "setup-spans.shelved.json")


@pytest.fixture
def registry(monkeypatch):
    """A registry of its own in the program's place."""
    from mmlspark_tpu.core import telemetry
    from mmlspark_tpu.core.telemetry.metrics import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(telemetry, "REGISTRY", reg)
    return reg


def ctx(setup_s=100.0):
    return types.SimpleNamespace(counters={"setup_s": setup_s})


def test_the_parts_of_set_up(registry):
    registry.gauge("setup.start_s").set(7.5)
    for name, stage, seconds in [
            ("xla.compile.trace.latency", "setup", 10.0),
            ("xla.compile.trace.latency", "run", 99.0),
            ("xla.compile.lower.latency", "setup", 2.5),
            ("xla.compile.latency", "setup", 30.0),
            ("xla.compile.latency", "setup", 1.0),
            ("xla.compile.latency", "run", 50.0)]:
        registry.histogram(name, stage=stage).observe(seconds)
    registry.incr("xla.compile.cache.hits.setup", 3)
    registry.incr("xla.compile.cache.misses.setup", 1)
    registry.incr("xla.compile.cache.unwritten.setup", 1)
    registry.incr("xla.compile.cache.misses.run", 40)
    got = {part: setup_stage.reduce(ctx(), part) for part in SIX.values()}
    assert got == {"start": 7.5, "trace_lower": 12.5, "compile": 31.0,
                   "unwritten": 1, "hit_frac": 75.0,
                   "unclaimed": pytest.approx(100.0 - 7.5 - 12.5 - 31.0)}
    # two threads compiling at once: the parts pass set-up and the
    # remainder says so, unclipped
    assert setup_stage.reduce(ctx(40.0), "unclaimed") == \
        pytest.approx(40.0 - 51.0)


def test_a_program_without_the_stage_has_nothing_to_read(registry):
    """The parent's sentry: histograms without a stage, no gauge, no
    cache counters."""
    for name in setup_stage.TRACE_LOWER + setup_stage.COMPILE:
        registry.histogram(name).observe(1.0)
    registry.incr("xla.compile.count", 5)
    assert {part: setup_stage.reduce(ctx(), part)
            for part in SIX.values()} == dict.fromkeys(SIX.values())
    with pytest.raises(ValueError):
        setup_stage.reduce(ctx(), "imports")


def test_the_metric_files_name_the_parts():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {x["name"]: x for x in json.load(f)["per_layer"]}
    for name, part in SIX.items():
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert (spec["reducer"], spec["args"]) == ("setup_stage",
                                                   {"part": part})
        assert listed[name]["workloads"] == ["lm-serve-chat"]
        assert (listed[name]["layer"], listed[name]["moves"]) == (
            "entry points", "setup_s")


@pytest.fixture(scope="module")
def chat():
    done = run_py("--workload", "lm-serve-chat", "--rehearse", "--trace", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(x) for x in done.stdout.splitlines() if x.strip()]


def test_the_open_loop_cell_reports_the_six(chat):
    last = chat[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(SIX) <= set(last["reduced"])
    assert {"tick_ms", "window_compiles"} <= set(last["reduced"])
    # in an open loop a traced run's tails hold the profiler's stall: the
    # cell does not report them (a chip run's window line notes them)
    assert not {"ttft_p95_ms", "itl_p95_ms"} & set(last["reduced"])
    # a CPU run names no timing, in the reducers' earlier lines either
    assert [k for x in chat if x.get("line") != "start"
            for k in timing_keys(x)] == []


@pytest.fixture(scope="module")
def merged(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("merged-setup"))
    with_shelved.merge(SHELVED, root)
    return root


def test_the_shelved_entries_list_the_other_cells():
    with open(SHELVED) as f:
        shelved = json.load(f)["per_layer"]
    assert [x["name"] for x in shelved] == list(SIX)
    others = [c for c in cells() if c != "lm-serve-chat"]
    assert all(x["workloads"] == others for x in shelved)


def test_the_merged_copy_checks_and_reports_the_six(merged):
    done = run_py("--check", root=merged)
    assert json.loads(done.stdout.splitlines()[-1])["check"] == "ok", \
        done.stdout
    with open(os.path.join(merged, "BENCHMARK.json")) as f:
        listed = {x["name"]: x for x in json.load(f)["per_layer"]}
    for name in SIX:
        assert sorted(listed[name]["workloads"]) == sorted(cells())
    done = run_py("--workload", "lm-serve-closed", "--rehearse", "--trace",
                  "1", root=merged)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True and set(SIX) <= set(last["reduced"])
