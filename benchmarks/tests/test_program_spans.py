"""The reducers that read the program's own spans, timers and totals, on
synthetic traces, counter tables and a registry primed by hand; and, merged
into a copy of the benchmark (`with_shelved.py`), through `run.py`."""
import json
import os
import types

import pytest

from lib import flops
from lib import trace as tr
from reducers import decode_roofline, registry_total, trace_idle_by_span
import with_shelved
from test_run import BENCH, ROOT, cells, run_py, timing_keys

PATTERN = r"^serving\.batcher\.(tick|admit|idle)(\.|$)"
TICK = "serving.batcher.tick"
SHELVED = os.path.join(BENCH, "metrics", "program-spans.shelved.json")


def ev(name, start, dur, detail=""):
    return (name, float(start), float(dur), detail)


def serving_trace():
    """One device over a slice of 10 s, busy 1-3 and 6-8: idle 0-1, 3-6 and
    8-10, six seconds.  The loop's spans: a tick 0-4 with a fetch 0.5-3.5
    inside it, a tick 4-9 with an admission 4-7 whose prefill is 5-6.5, and
    nothing over 9-10.  Another component's `serving.batcher.batch` lies
    over everything and must not count."""
    dev = [ev("fusion.1", 1, 2), ev("_paged_pallas.2", 6, 2)]
    host = [("bench.trace_slice", 0.0, 10.0, "python"),
            ("serving.batcher.batch", 0.0, 10.0, "python"),
            (TICK, 0.0, 4.0, "python"),
            (TICK + ".fetch", 0.5, 3.0, "python"),
            (TICK, 4.0, 5.0, "python"),
            (TICK + ".admit", 4.0, 3.0, "python"),
            ("serving.batcher.admit.prefill", 5.0, 1.5, "python")]
    return tr.Trace({0: dev}, host)


def ctx_for(trace, **units):
    t0, t1 = tr.window_of(trace, "bench.trace_slice")
    return types.SimpleNamespace(
        trace=trace.clip(t0, t1),
        slice={"t0": t0, "t1": t1, "seconds": t1 - t0, **units})


def last_line(capsys, name):
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.strip()]
    return [x for x in lines if x.get("line") == name][-1]


def test_innermost_splits_a_stretch_among_the_spans():
    spans = [("a", 0.0, 10.0), ("b", 2.0, 6.0), ("c", 3.0, 4.0),
             ("d", 12.0, 13.0)]
    assert trace_idle_by_span.innermost(spans) == [
        ("a", 0.0, 2.0), ("b", 2.0, 3.0), ("c", 3.0, 4.0), ("b", 4.0, 6.0),
        ("a", 6.0, 10.0), ("d", 12.0, 13.0)]


def test_idle_goes_to_the_innermost_matching_span(capsys):
    ctx = ctx_for(serving_trace(), ticks=2)
    value = trace_idle_by_span.reduce(ctx, pattern=PATTERN)
    # idle 0-1: tick 0-0.5, fetch 0.5-1.  idle 3-6: fetch 3-3.5, tick 3.5-4,
    # admit 4-5, prefill 5-6.  idle 8-10: tick 8-9, nothing 9-10.
    assert value == pytest.approx(100.0 * 5.0 / 6.0)
    line = last_line(capsys, "idle_by_phase")
    assert line["idle_s"] == pytest.approx(6.0)
    assert line["explained_s"] == pytest.approx(5.0)
    assert line[trace_idle_by_span.NO_SPAN] == pytest.approx(1.0)
    per_tick = {n: (p["idle_ms"], p["span_ms"])
                for n, p in line["phases"].items()}
    assert per_tick == {
        TICK: (pytest.approx(1000.0), pytest.approx(4500.0)),
        TICK + ".fetch": (pytest.approx(500.0), pytest.approx(1500.0)),
        TICK + ".admit": (pytest.approx(500.0), pytest.approx(1500.0)),
        "serving.batcher.admit.prefill": (pytest.approx(500.0),
                                          pytest.approx(750.0))}
    # the same idle seconds as trace_idle_share reports of this slice
    from reducers import trace_idle_share

    assert trace_idle_share.reduce(ctx) == pytest.approx(60.0)


@pytest.mark.parametrize("case", ["no trace", "no matching span",
                                  "no idle"])
def test_idle_by_span_with_nothing_to_read(case):
    if case == "no trace":
        ctx = types.SimpleNamespace(trace=None, slice={})
    elif case == "no matching span":      # the parent's program
        t = serving_trace()
        t.host = [h for h in t.host if not h[0].startswith(TICK)
                  and ".admit" not in h[0]]
        ctx = ctx_for(t, ticks=2)
    else:
        t = serving_trace()
        t.devices[0] = [ev("fusion.1", 0, 10)]
        ctx = ctx_for(t, ticks=2)
    assert trace_idle_by_span.reduce(ctx, pattern=PATTERN) is None


def roofline_ctx(counters, peaks=None):
    with open(cfg_path("gpt2-medium")) as f:
        config = json.load(f)
    return types.SimpleNamespace(
        counters=counters, config=config, params={"max_slots": 32},
        peaks=peaks or {"flops": 197e12, "hbm_bytes": 819e9})


def cfg_path(name):
    return os.path.join(BENCH, "configs", name + ".json")


ARGS = {"ticks": "hist.serving.batcher.tick.latency.count",
        "seconds": "hist.serving.batcher.tick.latency.sum",
        "live": "serving.batcher.live_tokens"}


def test_decode_roofline_known(capsys):
    # 100 ticks of 50 ms with 7,680 live tokens each: 32 slots at 240
    ctx = roofline_ctx({ARGS["ticks"]: 100.0, ARGS["seconds"]: 5.0,
                        ARGS["live"]: 768000.0})
    need = flops.decode_tick_bytes(ctx.config, 7680.0)
    # 0.71 GB of matmul weights and 0.75 GB of K/V rows
    assert need == pytest.approx(2 * 353_501_184 + 4 * 24 * 7680 * 1024)
    value = decode_roofline.reduce(ctx, **ARGS)
    assert value == pytest.approx(100.0 * (need / 819e9) / 0.05)
    line = last_line(capsys, "decode_roofline")
    assert line["bound"] == "bytes" and line["live_tokens_a_tick"] == 7680.0
    # many more rows a tick and the matmuls' FLOPs bind instead
    ctx.params = {"max_slots": 4096}
    decode_roofline.reduce(ctx, **ARGS)
    assert last_line(capsys, "decode_roofline")["bound"] == "flops"


@pytest.mark.parametrize("counters", [
    {}, {ARGS["ticks"]: 0.0, ARGS["seconds"]: 0.0, ARGS["live"]: 0.0},
    {ARGS["ticks"]: 10.0, ARGS["seconds"]: 1.0}])
def test_decode_roofline_with_nothing_to_read(counters, capsys):
    assert decode_roofline.reduce(roofline_ctx(counters), **ARGS) is None
    assert capsys.readouterr().out == ""


def test_registry_total_reads_process_totals():
    from mmlspark_tpu.core import telemetry

    names = ["bench.test.trace.latency", "bench.test.lower.latency"]
    assert registry_total.reduce(None, names=names) is None
    telemetry.histogram(names[0]).observe(1.5)
    telemetry.histogram(names[0]).observe(0.25)
    telemetry.histogram(names[1], stage="x").observe(2.0)
    telemetry.histogram("bench.test.other").observe(100.0)
    assert registry_total.reduce(None, names=names) == pytest.approx(3.75)
    assert registry_total.reduce(None, names=names[:1], scale=1e3) == \
        pytest.approx(1750.0)


@pytest.fixture(scope="module")
def merged(tmp_path_factory):
    """The benchmark as a PR that may edit the cells' files would leave it:
    metrics/program-spans.shelved.json merged into a copy."""
    root = str(tmp_path_factory.mktemp("merged"))
    with_shelved.merge(SHELVED, root)
    return root


def test_the_benchmark_itself_is_as_the_parent_left_it():
    """The shelved metrics are in no cell's file and not in BENCHMARK.json:
    run.py could not report them, and `--check` would refuse the entry."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {x["name"] for x in json.load(f)["per_layer"]}
    with open(SHELVED) as f:
        shelved = json.load(f)["per_layer"]
    assert len(shelved) == 8 and not listed & {x["name"] for x in shelved}
    for x in shelved:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           x["name"] + ".json"))
    done = run_py("--check")
    assert json.loads(done.stdout.splitlines()[-1])["check"] == "ok"


def test_check_takes_the_merged_manifest(merged):
    done = run_py("--check", root=merged)
    assert done.returncode == 0, done.stdout
    assert json.loads(done.stdout.splitlines()[-1])["check"] == "ok"
    with open(os.path.join(merged, "BENCHMARK.json")) as f:
        listed = {x["name"]: x for x in json.load(f)["per_layer"]}
    assert listed["trace_lower_s"]["workloads"] == cells()
    assert listed["trace_lower_s"]["moves"] == "setup_s"


def test_rehearse_reduces_the_program_counters(merged):
    done = run_py("--workload", "lm-serve-closed", "--rehearse",
                  "--trace", "1", root=merged)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert {"decode_tick_ms", "tick_host_ms", "admit_time_frac",
            "queue_wait_ms", "prefill_useful_frac", "decode_tick_roofline",
            "trace_lower_s"} <= set(last["reduced"])
    # the CPU backend has no device plane: nothing for the span reducer
    assert "idle_explained_frac" not in last["reduced"]
    # a CPU run names no timing, in the reducers' earlier lines either
    assert [k for x in lines if x.get("line") != "start"
            for k in timing_keys(x)] == []
