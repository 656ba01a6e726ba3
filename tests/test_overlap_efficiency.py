"""Feed-architecture overlap efficiency, as a structural check on the CPU.

Is the async double-buffered feed (TPUModel.run_chunk_iter; the
Batchers.scala:12-65 + CNTKModel.scala:88-140 overlap pattern) itself
efficient, whatever the host->device bandwidth?  On the local CPU
backend, the FULL ImageFeaturizer path — JPEG decode on the prefetch thread, chunk assembly,
sharded device_put, forward, async fetch — must reach >=70% of the
forward-only throughput of the SAME compiled program on device-resident
input.  That was round 1's acceptance bar for the feed design.
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
from mmlspark_tpu.parallel.mesh import batch_sharding

N = 96
SRC = 128          # source JPEG side; resized on device to the model's 112
BATCH = 32
MIN_RATIO = 0.70


def _mixed_tables():
    rng = np.random.default_rng(1)

    def jpeg(h, w):
        buf = io.BytesIO()
        Image.fromarray(rng.integers(0, 256, (h, w, 3), np.uint8)).save(
            buf, format="JPEG", quality=85)
        return buf.getvalue()

    mixed = Table({"image": [jpeg(*[(128, 128), (144, 128), (128, 160)][i % 3])
                             for i in range(48)]})
    mono = Table({"image": [jpeg(128, 128) for _ in range(48)]})
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
def test_e2e_feed_at_least_70pct_of_forward_only():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    blobs = []
    for _ in range(N):
        arr = rng.integers(0, 256, (SRC, SRC, 3), np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=85)
        blobs.append(buf.getvalue())
    table = Table({"image": blobs})

    # forward cost must dominate decode for the ratio to measure the FEED,
    # not the codec: resnet18 @ 112^2 is ~15ms/img on XLA-CPU vs ~1ms decode
    bundle = FlaxBundle("resnet18", {"num_classes": 10, "dtype": jnp.float32},
                        input_shape=(112, 112, 3), seed=0)
    feat = ImageFeaturizer(bundle=bundle, input_col="image",
                           output_col="features", batch_size=BATCH)

    # forward-only upper bound: the SAME cached executor program the e2e
    # path runs (preprocess fused), on an already-staged sharded batch
    model = feat._model_for(bundle, "image")
    dev_vars, jitted, mesh = model._executor(bundle, model._fetch_name(bundle))
    bs, _ = model.chunk_sizes(N, mesh.shape["data"])
    xs = rng.integers(0, 256, (bs, SRC, SRC, 3), np.uint8)
    x = jax.device_put(xs, batch_sharding(mesh, xs.ndim))
    jax.block_until_ready(jitted(dev_vars, x))  # compile once
    fwd_dt = None
    for _ in range(3):  # best-of-3: the 1-core host is noisy
        t0 = time.perf_counter()
        for _ in range(3):
            y = jitted(dev_vars, x)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        fwd_dt = dt if fwd_dt is None else min(fwd_dt, dt)
    fwd_ips = 3 * bs / fwd_dt

    out = feat.transform(table)  # warm (shares the compiled program above)
    assert out["features"].shape[0] == N
    e2e_dt = None
    for _ in range(3):
        t0 = time.perf_counter()
        feat.transform(table)
        dt = time.perf_counter() - t0
        e2e_dt = dt if e2e_dt is None else min(e2e_dt, dt)
    e2e_ips = N / e2e_dt

    ratio = e2e_ips / fwd_ips
    assert ratio >= MIN_RATIO, (
        f"feed overhead too high: e2e {e2e_ips:.1f} img/s is only "
        f"{ratio:.0%} of forward-only {fwd_ips:.1f} img/s")
