"""Feed microbench: naive per-chunk device_put vs the DeviceFeed paths.

Measures the quantities the engine exists to improve, on whatever
backend is attached (the chip for real numbers; CPU for the structural
check tests/test_device_feed.py asserts):

  transfer_calls : fixed per-transfer costs paid
  wall_s / ips   : end wall time for transfer+compute of every chunk
  shard_gbps / transfer_concurrency : the sharded path's per-shard
                   bandwidth and its transfer pool's in-flight high-water
  wire_ratio     : raw/sent bytes on the compressed RLE wire

    python tools/feed_bench.py [--images 256] [--chunks 16] [--side 224]
                               [--depth 2] [--coalesce 8]
                               [--sharded] [--coalesced] [--compressed]

The three transfer paths are A/B-able from this one harness: pass any
subset of `--sharded / --coalesced / --compressed` (default: coalesced
only — the PR-2 shape, and what `tools/ci.py feed-bench` smokes plus
`--sharded --compressed` on the virtual mesh).  Prints one JSON object:
{"naive": {...}, "coalesced": {...}, "sharded": {...},
"compressed": {...}, "speedup", "transfer_call_ratio"} with absent modes
omitted.  The acceptance bar from ISSUE 2 is transfer_call_ratio >= 4
for 256 images in 16 chunks; ISSUE 14's multi-device bar is sharded
h2d_gbps >= 4x coalesced on real hardware.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run_naive(chunks, compute):
    import jax

    outs = []
    t0 = time.perf_counter()
    for c, n in chunks:
        x = jax.device_put(c)
        outs.append((compute(x), n))
    res = [np.asarray(y)[:n] for y, n in outs]
    return res, time.perf_counter() - t0, len(chunks)


def _run_feed(chunks, compute, depth, coalesce, tel):
    from mmlspark_tpu.io.feed import DeviceFeed

    feed = DeviceFeed(depth=depth, coalesce=coalesce, telemetry=tel,
                      shard_strategy="coalesced")
    t0 = time.perf_counter()
    res = feed.run(iter(chunks), compute, greedy=False)
    return res, time.perf_counter() - t0


def _run_sharded(chunks, compute, tel):
    """Every chunk through the per-shard engine on a data mesh (chunks
    are sized divisible by the device count), computed and drained like
    the other paths so wall times compare."""
    import jax

    from mmlspark_tpu.io.feed import DeviceFeed
    from mmlspark_tpu.parallel.mesh import batch_sharding, make_mesh

    mesh = make_mesh()
    feed = DeviceFeed(mesh=mesh, telemetry=tel, shard_strategy="sharded")
    t0 = time.perf_counter()
    outs = []
    for c, n in chunks:
        sh = batch_sharding(mesh, c.ndim)
        outs.append((compute(feed.put(c, sh)), n))
    res = [np.asarray(y)[:n] for y, n in outs]
    return res, time.perf_counter() - t0


def _run_compressed(chunks, compute, tel):
    """Chunks RLE-encoded host-side, shipped on the compressed wire and
    decoded on device.  Encode time is charged to the wall on purpose:
    the wire win has to beat it to count."""
    from mmlspark_tpu.io.feed import DeviceFeed
    from mmlspark_tpu.ops.wire_codec import rle_encode

    feed = DeviceFeed(telemetry=tel, shard_strategy="compressed")
    t0 = time.perf_counter()
    outs = []
    for c, n in chunks:
        (x,) = feed.put_group([rle_encode(c)])
        outs.append((compute(x), n))
    res = [np.asarray(y)[:n] for y, n in outs]
    return res, time.perf_counter() - t0


def _section(images, res_naive, res, wall_s, tel):
    from mmlspark_tpu.io.feed import FeedTelemetry

    for a, b in zip(res_naive, res):
        np.testing.assert_array_equal(a, np.asarray(b))
    snap = tel.snapshot()
    return {
        "wall_s": round(wall_s, 4),
        "ips": round(images / wall_s, 1) if wall_s > 0 else None,
        "transfer_calls": int(snap["transfer_calls"]),
        **FeedTelemetry.summarize(snap),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--images", type=int, default=256)
    ap.add_argument("--chunks", type=int, default=16)
    ap.add_argument("--side", type=int, default=224)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--coalesce", type=int, default=8)
    ap.add_argument("--sharded", action="store_true",
                    help="bench the per-shard direct-to-chip path")
    ap.add_argument("--coalesced", action="store_true",
                    help="bench the packed single-put path")
    ap.add_argument("--compressed", action="store_true",
                    help="bench the RLE compressed-wire path")
    args = ap.parse_args(argv)
    if not (args.sharded or args.coalesced or args.compressed):
        args.coalesced = True

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.io.feed import FeedTelemetry

    bs = args.images // args.chunks
    if args.sharded:
        # the sharded path needs the batch divisible by the data degree
        dp = len(jax.devices())
        bs = max(dp, (bs // dp) * dp)
    rng = np.random.default_rng(0)
    # flat gray 8-pixel blocks: byte-runnable like real decoded images'
    # flat regions.  Pointwise-random or RGB-interleaved pixels average
    # byte runs < 2 and would bench only the codec's worst case
    # (tests/test_wire_codec.py measures both).
    blk = 8
    side = max(blk, (args.side // blk) * blk)
    chunks = [((rng.integers(0, 6, (bs, side, side // blk, 1)) * 40)
               .astype(np.uint8).repeat(blk, axis=2).repeat(3, axis=3), bs)
              for _ in range(args.chunks)]
    images = bs * args.chunks

    # cheap on-device reduction: enough compute to overlap against, not
    # enough to hide a slow feed entirely
    @jax.jit
    def compute(x):
        return jnp.asarray(x, jnp.float32).mean(axis=(1, 2, 3))

    # warm every requested path (compile outside the timed region)
    _run_naive(chunks[:1], compute)
    warm = chunks[: min(2, len(chunks))]
    if args.coalesced:
        _run_feed(warm, compute, args.depth, args.coalesce, FeedTelemetry())
    if args.sharded:
        _run_sharded(warm, compute, FeedTelemetry())
    if args.compressed:
        _run_compressed(warm, compute, FeedTelemetry())

    naive_res, naive_s, naive_calls = _run_naive(chunks, compute)
    out = {
        "platform": jax.devices()[0].platform,
        "devices": len(jax.devices()),
        "images": images, "chunks": args.chunks,
        "depth": args.depth, "coalesce": args.coalesce,
        "naive": {"wall_s": round(naive_s, 4),
                  "ips": round(images / naive_s, 1),
                  "transfer_calls": naive_calls},
    }
    if args.coalesced:
        tel = FeedTelemetry()
        res, wall = _run_feed(chunks, compute, args.depth, args.coalesce,
                              tel)
        out["coalesced"] = _section(images, naive_res, res, wall, tel)
        out["speedup"] = round(naive_s / wall, 3)
        out["transfer_call_ratio"] = round(
            naive_calls / max(out["coalesced"]["transfer_calls"], 1), 2)
    if args.sharded:
        tel = FeedTelemetry()
        res, wall = _run_sharded(chunks, compute, tel)
        out["sharded"] = _section(images, naive_res, res, wall, tel)
    if args.compressed:
        tel = FeedTelemetry()
        res, wall = _run_compressed(chunks, compute, tel)
        out["compressed"] = _section(images, naive_res, res, wall, tel)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
