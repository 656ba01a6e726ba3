"""The trace reducer on synthetic intervals with known answers, and on the
fixture recorded on the chip."""
import os
import types

import pytest

from lib import trace as tr
from reducers import (trace_exposed_collectives, trace_idle_share,
                      trace_op_time)

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


def ev(name, start, dur, detail=""):
    return (name, float(start), float(dur), detail)


def synthetic():
    """Device 0 over a window of 10 s: busy 0-2, 3-5 (two overlapping
    ops), an all-reduce 5-7 of which 6-7 is hidden under a fusion, idle
    elsewhere.  A `while` container spans everything and must not count."""
    dev0 = [ev("while.1", 0, 10), ev("fusion.1", 0, 2),
            ev("custom-call.3", 3, 1.5, "jit(f)/flash"),
            ev("fusion.2", 4, 1), ev("all-reduce.1", 5, 2),
            ev("fusion.3", 6, 1)]
    dev1 = [ev("fusion.1", 0, 5)]
    host = [("bench.trace_slice", 0.0, 10.0, "main"),
            ("bench.step", 0.0, 2.5, "main"), ("bench.step", 2.5, 5.0, "main"),
            ("bench.fetch", 7.0, 3.0, "main")]
    return tr.Trace({0: dev0, 1: dev1}, host)


def ctx_for(trace, **units):
    t0, t1 = tr.window_of(trace, "bench.trace_slice")
    return types.SimpleNamespace(
        trace=trace.clip(t0, t1),
        slice={"t0": t0, "t1": t1, "seconds": t1 - t0, **units})


def test_interval_arithmetic():
    assert tr.merge([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.total([(0, 2.5), (3, 4)]) == 3.5
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 1), (5, 6)], [(0.5, 5.5)]) == [(0, 0.5), (5.5, 6)]


def test_busy_skips_containers():
    t = synthetic()
    assert tr.busy(t.devices[0]) == [(0, 2), (3, 7)]
    assert tr.busy_seconds(t) == {0: 6.0, 1: 5.0}


def test_idle_share_known():
    ctx = ctx_for(synthetic())
    # device 0 idle 4 of 10, device 1 idle 5 of 10
    assert trace_idle_share.reduce(ctx) == pytest.approx(45.0)


def test_op_time_known():
    ctx = ctx_for(synthetic(), steps=2)
    # the flash custom call ran 1.5 s on device 0, nothing on device 1:
    # mean over devices 0.75 s, over 2 steps, in ms
    assert trace_op_time.reduce(ctx, pattern="flash", per="steps") == \
        pytest.approx(375.0)
    assert trace_op_time.reduce(ctx, pattern="^fusion", per="steps",
                                exclude=r"fusion\.3") == pytest.approx(
        1e3 * ((2 + 1) + 5) / 2 / 2)
    assert trace_op_time.reduce(ctx, pattern="flash", per="ticks") is None


def test_exposed_collectives_known():
    t = synthetic()
    ran, exposed = tr.exposed_seconds(t.devices[0])
    assert (ran, exposed) == (2.0, 1.0)
    assert trace_exposed_collectives.reduce(ctx_for(t)) == pytest.approx(10.0)


def test_top_ops_and_gaps():
    t = synthetic()
    top = tr.top_ops(t.devices[0], n=3)
    # by stem: fusion.1, .2 and .3 are one entry of 2 + 1 + 1 s
    assert top[0] == ["fusion", 4.0] and top[1] == ["all-reduce", 2.0]
    assert top[-1] == ["(all other ops)", 1.5]
    gaps = dict(tr.idle_gaps(t, 0, 0.0, 10.0, ignore=("bench.trace_slice",)))
    # 2-3 falls inside the second bench.step (2.5-7.5) by overlap, 7-10 to
    # bench.fetch
    assert gaps == {"bench.fetch": pytest.approx(3.0),
                    "bench.step": pytest.approx(1.0)}


def test_clip_and_json_round_trip(tmp_path):
    t = synthetic().clip(1.0, 6.5)
    assert tr.busy(t.devices[0]) == [(1.0, 2.0), (3.0, 6.5)]
    path = str(tmp_path / "t.json.gz")
    t.save(path)
    back = tr.Trace.load(path)
    assert back.devices == t.devices and back.host == t.host


def test_no_trace_is_nothing_to_read():
    ctx = types.SimpleNamespace(trace=None, slice={})
    assert trace_idle_share.reduce(ctx) is None
    assert trace_op_time.reduce(ctx, pattern="x", per="steps") is None
    assert trace_exposed_collectives.reduce(ctx) is None


@pytest.mark.parametrize("name", sorted(
    f[:-len(".expect.json")]
    for f in (os.listdir(FIXTURES) if os.path.isdir(FIXTURES) else [])
    if f.endswith(".expect.json")))
def test_recorded_fixture(name):
    """A slice of a trace recorded on the chip, with the numbers the
    reducers gave when it was recorded."""
    import json

    with open(os.path.join(FIXTURES, name + ".expect.json")) as f:
        want = json.load(f)
    trace = tr.Trace.load(os.path.join(FIXTURES, name + ".trace.json.gz"))
    ctx = types.SimpleNamespace(trace=trace, slice=want["slice"])
    assert trace_idle_share.reduce(ctx) == pytest.approx(want["idle_pct"],
                                                         rel=1e-9)
    for case in want["op_time"]:
        assert trace_op_time.reduce(ctx, **case["args"]) == pytest.approx(
            case["ms"], rel=1e-9)
    if "exposed_pct" in want:
        assert trace_exposed_collectives.reduce(ctx) == pytest.approx(
            want["exposed_pct"], rel=1e-9)
    assert len(tr.top_ops(trace.devices[min(trace.devices)])) <= 10
