"""HostPipeline: ordering, structural overlap, backpressure,
feed integration, fault injection, and telemetry — all timing-free
(events and counters, never wall-clock comparisons) so nothing here can
flake on a loaded single-core host."""
import queue
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.core import telemetry as T
from mmlspark_tpu.io.feed import DeviceFeed, FeedTelemetry
from mmlspark_tpu.io.pipeline import (
    _EOF,
    PIPELINE_TELEMETRY,
    HostPipeline,
    PipelineStage,
    PipelineTelemetry,
    pipeline_workers,
)


def _drain(pipe):
    """Manual consumer for tests that `start()` themselves."""
    out = []
    while True:
        item = pipe._next_out()
        if isinstance(item, _EOF):
            return out
        out.append(item[1])


# ---- ordering --------------------------------------------------------------

def test_multiworker_output_stays_ordered():
    """4 workers complete out of order (staggered stage latency); the
    reorder buffer must still emit results in sequence."""
    def fn(x):
        if x % 3 == 0:
            time.sleep(0.01)  # make later items overtake earlier ones
        return x * 10
    pipe = HostPipeline([PipelineStage("jitter", fn, workers=4)])
    assert list(pipe.run(range(24))) == [x * 10 for x in range(24)]


def test_two_stage_composition_ordered():
    pipe = HostPipeline([
        PipelineStage("a", lambda x: x + 1, workers=3),
        PipelineStage("b", lambda x: x * 2, workers=2),
    ])
    assert list(pipe.run(range(17))) == [(x + 1) * 2 for x in range(17)]


def test_empty_and_single_item_streams():
    assert list(HostPipeline([PipelineStage("a", str)]).run([])) == []
    assert list(HostPipeline([PipelineStage("a", str)]).run([7])) == ["7"]


def test_single_use_instances():
    pipe = HostPipeline([PipelineStage("a", str)])
    list(pipe.run([1]))
    with pytest.raises(RuntimeError, match="single-use"):
        list(pipe.run([2]))


# ---- structural overlap / backpressure -------------------------------------

def test_stage_runs_ahead_while_next_is_blocked():
    """THE overlap property, event-synchronized: while stage b is parked
    inside its first item, stage a must keep producing — its output
    queue reaches depth >= 2 (the high-water witness bench/tests use)."""
    a_done = threading.Event()
    b_gate = threading.Event()
    b_entered = threading.Event()
    n_a = []

    def stage_a(x):
        n_a.append(x)
        if len(n_a) >= 3:
            a_done.set()
        return x

    def stage_b(x):
        b_entered.set()
        assert b_gate.wait(10)
        return x

    pipe = HostPipeline([PipelineStage("a", stage_a, workers=2),
                         PipelineStage("b", stage_b)], queue_size=4)
    pipe.start(range(8))
    assert b_entered.wait(5)
    assert a_done.wait(5), "stage a did not run ahead of the blocked b"
    b_gate.set()
    assert _drain(pipe) == list(range(8))
    assert pipe.high_water().get("b", 0) >= 2, pipe.high_water()


def test_backpressure_bounds_producer_runahead():
    """With the consumer stage parked, the producer must stall at the
    bounded queue — memory stays O(queue_size), never O(dataset).
    (Waiting LONGER can only make this stricter, so it cannot flake.)"""
    gate = threading.Event()
    entered = threading.Event()
    produced = []

    def items():
        for i in range(1000):
            produced.append(i)
            yield i

    def parked(x):
        entered.set()
        assert gate.wait(10)
        return x

    pipe = HostPipeline([PipelineStage("parked", parked, workers=1)],
                        queue_size=2)
    pipe.start(items())
    assert entered.wait(5)
    time.sleep(0.3)  # every chance to (wrongly) run ahead
    # bound: queue_size in the stage queue + 1 in the worker's hand +
    # 1 in the producer's hand
    assert len(produced) <= 2 + 2, f"producer ran ahead: {len(produced)}"
    gate.set()
    assert _drain(pipe) == list(range(1000))
    assert pipe.high_water()["parked"] <= 2


# ---- DeviceFeed integration ------------------------------------------------

def test_feed_source_drives_device_feed_in_order(rng):
    """N pipeline decode workers feed DeviceFeed.run: results must be
    per-chunk exact, in feed order, with every chunk fed."""
    import jax.numpy as jnp

    hosts = [rng.integers(0, 255, (4, 6, 6, 3)).astype(np.uint8)
             for _ in range(10)]

    def make(i):
        return hosts[i], 4 - (i % 2)

    def compute(x):
        return jnp.asarray(x, jnp.float32) * 2.0

    naive = [np.asarray(compute(c))[:n] for c, n in map(make, range(10))]
    pipe = HostPipeline([PipelineStage("decode", make, workers=3)])
    tel = FeedTelemetry()
    feed = DeviceFeed(depth=2, coalesce=4, telemetry=tel)
    got = feed.run(pipe.feed_source(range(10)), compute, greedy=False)
    assert len(got) == 10
    for g, ref in zip(got, naive):
        np.testing.assert_array_equal(g, ref)
    assert tel.snapshot()["chunks_fed"] == 10


def test_plain_iterable_signature_still_works(rng):
    """The PR-2 calling convention (a bare generator) must keep working
    — `run` wraps it in the single-prefetch-thread _IterSource."""
    import jax.numpy as jnp

    chunks = ((rng.integers(0, 255, (2, 4)).astype(np.uint8), 2)
              for _ in range(5))
    got = DeviceFeed(depth=2, telemetry=FeedTelemetry()).run(
        chunks, lambda x: jnp.asarray(x, jnp.int32) + 1)
    assert len(got) == 5


# ---- failure semantics -----------------------------------------------------

def test_stage_error_propagates_to_run_consumer():
    def boom(x):
        if x == 5:
            raise ValueError("decode exploded")
        return x
    pipe = HostPipeline([PipelineStage("boom", boom, workers=2)])
    with pytest.raises(ValueError, match="decode exploded"):
        list(pipe.run(range(20)))
    assert isinstance(pipe.error, ValueError)


def test_producer_error_propagates():
    def items():
        yield 1
        raise OSError("source went away")
    pipe = HostPipeline([PipelineStage("a", lambda x: x)])
    with pytest.raises(OSError, match="source went away"):
        list(pipe.run(items()))


def test_stage_error_propagates_through_feed(rng):
    """An error mid-pipeline must surface from DeviceFeed.run — after
    in-flight groups drain, not as a deadlock or silent truncation."""
    def boom(i):
        if i == 3:
            raise ValueError("mid-pipeline boom")
        return rng.integers(0, 255, (2, 4)).astype(np.uint8), 2
    pipe = HostPipeline([PipelineStage("boom", boom)])
    feed = DeviceFeed(depth=2, telemetry=FeedTelemetry())
    with pytest.raises(ValueError, match="mid-pipeline boom"):
        feed.run(pipe.feed_source(range(10)), lambda x: x)


def test_abandoned_consumer_does_not_strand_workers():
    """Closing the run() generator early cancels the pipeline; its
    daemon workers exit their poll loops instead of blocking forever."""
    pipe = HostPipeline([PipelineStage("a", lambda x: x)], queue_size=2)
    gen = pipe.run(range(100))
    assert next(gen) == 0
    gen.close()
    assert pipe._cancelled.is_set()


@pytest.mark.chaos
def test_fault_mid_pipeline_degrades_without_deadlock_or_loss(rng):
    """feed.device_put failing mid-stream (utils/faults.py) while a
    HostPipeline is driving the feed: the packed transfer exhausts its
    retries, the engine DEGRADES to unpipelined per-chunk puts, and
    every chunk still comes back correct and in order — no deadlock, no
    dropped batch."""
    from mmlspark_tpu.utils.faults import FAULTS, FaultPlan

    import jax.numpy as jnp

    chunks = [(rng.integers(0, 255, (4, 8, 8, 3)).astype(np.uint8), 4)
              for _ in range(8)]

    def compute(x):
        return jnp.asarray(x, jnp.float32).sum(axis=(1, 2, 3))

    naive = [np.asarray(compute(c))[:n] for c, n in chunks]
    pipe = HostPipeline([PipelineStage("decode", lambda i: chunks[i],
                                       workers=2)])
    feed = DeviceFeed(depth=2, coalesce=4, telemetry=FeedTelemetry())
    plan = FaultPlan(seed=5).on("feed.device_put", probability=1.0,
                                max_failures=4)
    with pytest.warns(RuntimeWarning, match="degraded"):
        with FAULTS.arm(plan):
            got = feed.run(pipe.feed_source(range(8)), compute,
                           greedy=False)
    assert feed.degraded
    assert len(got) == 8
    for g, ref in zip(got, naive):
        np.testing.assert_array_equal(g, ref)


# ---- telemetry / spans -----------------------------------------------------

def test_stage_telemetry_and_metrics_accumulate():
    tel = PipelineTelemetry()
    before = T.counters().get("io.pipeline.items.work", 0)
    pipe = HostPipeline([PipelineStage("work", lambda x: x)],
                        telemetry=tel)
    list(pipe.run(range(6)))
    snap = tel.snapshot()
    assert snap["work"]["items"] == 6
    assert snap["work"]["busy_s"] >= 0
    assert T.counters().get("io.pipeline.items.work", 0) == before + 6
    # the delta shape the benchmark's reducers consume
    d = tel.delta({"work": {"busy_s": 0.0, "items": 1.0}})
    assert d["work"]["items"] == 5


def test_process_sink_is_shared_default():
    before = PIPELINE_TELEMETRY.snapshot()
    list(HostPipeline([PipelineStage("shared", str)]).run(range(3)))
    d = PIPELINE_TELEMETRY.delta(before)
    assert d["shared"]["items"] == 3


def test_spans_recorded_under_active_trace():
    """Stage items run on worker threads but must attach to the trace
    active where the pipeline was STARTED — /trace/<id> then shows
    decode/forward spans of different batches side by side."""
    with T.span("pipeline-test"):
        tid = T.current_trace_id()
        pipe = HostPipeline([PipelineStage("a", lambda x: x),
                             PipelineStage("b", lambda x: x)])
        assert list(pipe.run(range(5))) == list(range(5))
    names = [s["name"] for s in T.get_trace(tid)]
    assert names.count("pipeline.a") == 5
    assert names.count("pipeline.b") == 5
    seqs = sorted(s["attrs"]["seq"] for s in T.get_trace(tid)
                  if s["name"] == "pipeline.a")
    assert seqs == list(range(5))


def test_no_spans_without_active_trace():
    t0 = len(T.recent_spans())
    list(HostPipeline([PipelineStage("quiet", str)]).run(range(3)))
    assert len(T.recent_spans()) == t0


# ---- decode_cells short-circuit (ops/image_stages.py) ----------------------

def test_decode_cells_short_circuits_decoded_rows(monkeypatch):
    """dict image rows and ndarray pixels must bypass the codec pool
    entirely; only encoded-bytes cells pay _decode_cell."""
    from mmlspark_tpu.io.image import array_to_image_row, image_row_to_array
    from mmlspark_tpu.ops import image_stages

    calls = []
    orig = image_stages._decode_cell

    def counting(v):
        calls.append(type(v).__name__)
        return orig(v)

    monkeypatch.setattr(image_stages, "_decode_cell", counting)
    arr = np.arange(24, dtype=np.uint8).reshape(2, 4, 3)
    row = array_to_image_row(arr * 2)
    col = np.empty(4, dtype=object)
    col[0] = row            # already an image row
    col[1] = arr            # already pixels
    col[2] = None           # missing
    col[3] = b"\x00garbage"  # only this one may hit the codec
    out = image_stages.decode_cells(col)
    assert out[0] is row
    np.testing.assert_array_equal(image_row_to_array(out[1]), arr)
    assert out[2] is None
    assert calls == ["bytes"], calls


# ---- worker-count knob -----------------------------------------------------

def test_pipeline_workers_env_override(monkeypatch):
    monkeypatch.delenv("MMLSPARK_PIPELINE_WORKERS", raising=False)
    assert pipeline_workers(3) == 3
    assert pipeline_workers() >= 1
    monkeypatch.setenv("MMLSPARK_PIPELINE_WORKERS", "7")
    assert pipeline_workers() == 7
    assert pipeline_workers(2) == 7  # env wins over the caller default
    monkeypatch.setenv("MMLSPARK_PIPELINE_WORKERS", "bogus")
    assert pipeline_workers(2) == 2
