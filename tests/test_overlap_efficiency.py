"""Feed-architecture overlap, as structural checks on the CPU.

Does the async double-buffered feed (TPUModel.run_chunk_iter; the
Batchers.scala:12-65 + CNTKModel.scala:88-140 overlap pattern) overlap
at all, and do shape groups share one in-flight window?  Counts and
event order on the local CPU backend; how much the overlap is worth is a
chip's number (`feed_overlap_frac`, PERF.md section 3).
"""
import io
import time

import numpy as np
import pytest
from PIL import Image

from mmlspark_tpu import Table
from mmlspark_tpu.io.feed import FEED_END, FeedSource
from mmlspark_tpu.models.bundle import FlaxBundle
from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
from mmlspark_tpu import native

N = 96
SRC = 128          # source JPEG side; resized on device to the model's 112
# six chunks of the N rows: more than one transfer can coalesce (4), so
# the table cannot go up in a single group however fast the decode is
BATCH = 16


def _jpeg(rng, h, w):
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 256, (h, w, 3), np.uint8)).save(
        buf, format="JPEG", quality=85)
    return buf.getvalue()


def _mixed_tables():
    rng = np.random.default_rng(1)
    sizes = [(128, 128), (144, 128), (128, 160)]
    mixed = Table({"image": [_jpeg(rng, *sizes[i % 3]) for i in range(48)]})
    mono = Table({"image": [_jpeg(rng, 128, 128) for _ in range(48)]})
    return mixed, mono


@pytest.mark.skipif(not native.jpeg_available(),
                    reason="needs the native JPEG decoder (streaming path)")
def test_mixed_shape_groups_share_one_feed_window(monkeypatch):
    """Shape-grouped input must flow through ONE bounded in-flight window
    (TPUModel.run_grouped): a per-group pipeline drain (the pre-round-5
    behavior) opened one window per shape group, paying a warm-up bubble
    and a full drain at every group boundary.  Structural proof, immune
    to 1-core CI timing noise: count feed-window invocations while the
    three shape groups' chunks all flow through it."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.tpu_model import TPUModel

    mixed, _ = _mixed_tables()
    bundle = FlaxBundle("resnet18", {"num_classes": 10, "dtype": jnp.float32},
                        input_shape=(112, 112, 3), seed=0)
    feat = ImageFeaturizer(bundle=bundle, input_col="image",
                           output_col="features", batch_size=16)

    windows = []          # one entry per feed-window (run_chunk_iter) call
    chunk_shapes = set()  # source shapes of the chunks that flowed through
    orig = TPUModel.run_chunk_iter

    def record(item):
        if item is not FEED_END:
            padded, _n = item
            chunk_shapes.add(tuple(padded.shape[1:]))
        return item

    def counted(self, chunk_iter, jitted, dev_vars, mesh):
        windows.append(1)
        if isinstance(chunk_iter, FeedSource):
            # the streaming path hands a pipeline-backed FeedSource, not
            # an iterable: tap its pull methods instead
            orig_get = chunk_iter.get
            orig_get_nowait = chunk_iter.get_nowait
            chunk_iter.get = lambda: record(orig_get())
            chunk_iter.get_nowait = lambda: record(orig_get_nowait())
            return orig(self, chunk_iter, jitted, dev_vars, mesh)

        def spy():
            for padded, n in chunk_iter:
                chunk_shapes.add(tuple(padded.shape[1:]))
                yield padded, n

        return orig(self, spy(), jitted, dev_vars, mesh)

    monkeypatch.setattr(TPUModel, "run_chunk_iter", counted)
    out = feat.transform(mixed)
    assert out["features"].shape[0] == 48
    assert len(chunk_shapes) == 3, (
        f"expected 3 decode shape groups, saw {sorted(chunk_shapes)}")
    assert len(windows) == 1, (
        f"{len(windows)} feed windows opened for 3 shape groups — the "
        "groups are not sharing one bounded in-flight window")


@pytest.mark.slow
@pytest.mark.skipif(not native.jpeg_available(),
                    reason="needs the native JPEG decoder (streaming path)")
def test_mixed_shape_groups_timing_stays_bounded():
    """Timing companion to the structural window check (slow: wall-clock
    ratios flake on the 1-core CI host, so the margin is wide — 3 serial
    per-group pipelines with drain bubbles measured well above 3x)."""
    import jax.numpy as jnp

    mixed, mono = _mixed_tables()
    bundle = FlaxBundle("resnet18", {"num_classes": 10, "dtype": jnp.float32},
                        input_shape=(112, 112, 3), seed=0)
    feat = ImageFeaturizer(bundle=bundle, input_col="image",
                           output_col="features", batch_size=16)
    for t in (mixed, mono):
        feat.transform(t)  # warm: compile every shape group's program
    times = {}
    for name, t in (("mixed", mixed), ("mono", mono)):
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            feat.transform(t)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        times[name] = best
    ratio = times["mixed"] / times["mono"]
    assert ratio < 3.0, (
        f"mixed-shape e2e is {ratio:.2f}x the single-shape time — "
        "the shape groups are not sharing one feed window")


@pytest.mark.skipif(not native.jpeg_available(),
                    reason="needs the native JPEG decoder (streaming path)")
@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["depth_in_flight", "nothing_in_flight"])
def test_feed_transfers_while_a_forward_is_in_flight(monkeypatch, pipelined):
    """The full ImageFeaturizer path (JPEG decode off the consumer thread,
    chunk assembly, device_put, forward, async fetch) overlaps at all:
    between the dispatch of some chunk's forward and the fetch of its
    output, the feed issued at least one more transfer.  Read from
    FEED_TELEMETRY's `transfer_calls` at those two instants: counts and
    order, no clock.  The control is the same path over a feed on its
    unpipelined rung (`degraded`: singleton groups, nothing kept in
    flight), which must show no such forward."""
    import jax.numpy as jnp

    from mmlspark_tpu.io.feed import FEED_TELEMETRY, DeviceFeed
    from mmlspark_tpu.models.tpu_model import TPUModel

    if not pipelined:
        feed_init = DeviceFeed.__init__

        def unpipelined(self, *args, **kwargs):
            feed_init(self, *args, **kwargs)
            self.degraded = True

        monkeypatch.setattr(DeviceFeed, "__init__", unpipelined)

    rng = np.random.default_rng(0)
    table = Table({"image": [_jpeg(rng, SRC, SRC) for _ in range(N)]})
    bundle = FlaxBundle("resnet18", {"num_classes": 10, "dtype": jnp.float32},
                        input_shape=(112, 112, 3), seed=0)
    feat = ImageFeaturizer(bundle=bundle, input_col="image",
                           output_col="features", batch_size=BATCH)

    def transfers():
        return FEED_TELEMETRY.snapshot()["transfer_calls"]

    spans = []   # per forward: [transfers at dispatch, transfers at fetch]

    class Tracked:
        def __init__(self, y):
            self.y = y
            self.span = [transfers(), None]
            spans.append(self.span)

        def copy_to_host_async(self):
            self.y.copy_to_host_async()

        def __array__(self, *args, **kwargs):
            self.span[1] = transfers()
            return np.asarray(self.y, *args, **kwargs)

    orig = TPUModel.run_chunk_iter

    def tracked(self, chunk_iter, jitted, dev_vars, mesh):
        return orig(self, chunk_iter,
                    lambda dv, x: Tracked(jitted(dv, x)), dev_vars, mesh)

    monkeypatch.setattr(TPUModel, "run_chunk_iter", tracked)
    before = transfers()
    out = feat.transform(table)
    assert out["features"].shape[0] == N
    assert len(spans) >= 2 and all(s[1] is not None for s in spans), spans
    assert transfers() - before >= 2, "the whole table went up in one transfer"
    overlapped = any(fetched > dispatched for dispatched, fetched in spans)
    assert overlapped == pipelined, (
        f"pipelined={pipelined}, yet (transfer_calls at dispatch, at fetch) "
        f"of each forward read: {spans}")
