"""Feed autotuner: sweep chunk size x depth x shard strategy, record the
winner into the feed's config.

The right feed shape depends on the host-to-device path, not the code:
a high fixed cost per transfer wants deep pipelines and huge coalesced
packs, a multi-chip host wants per-shard parallel puts, and a thin wire
wants the RLE compressed path's encode tax.  Rather than hardcode one guess, this
tool measures every combination on a synthetic workload shaped like the
real one and persists the winner:

    python tools/feed_tune.py [--images 256] [--side 224]
                              [--chunk-sizes 16,32,64] [--depths 1,2,4]
                              [--strategies coalesced,sharded]
                              [--out FEED_TUNED.json] [--trials 2]

The winner JSON ({"chunk": .., "depth": .., "coalesce": .., "strategy":
..}) is written atomically (tmp + fsync + rename) to `--out`; point
MMLSPARK_FEED_TUNED at that file and every `DeviceFeed` constructed with
default knobs adopts it (`io.feed.load_tuned`).  Pass `--out ''` to
sweep without persisting.  Prints one JSON object with the full sweep
table and the winner.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _make_chunks(images: int, chunk: int, side: int, rng):
    """Flat gray-block pixels: byte-runnable like real
    decoded images — the compressed strategy needs representative run
    lengths, not pointwise noise."""
    bs = max(1, chunk)
    n = max(1, images // bs)
    blk = 8
    side = max(blk, (side // blk) * blk)
    return [((rng.integers(0, 6, (bs, side, side // blk, 1)) * 40)
             .astype(np.uint8).repeat(blk, axis=2).repeat(3, axis=3), bs)
            for _ in range(n)]


def _wall(strategy: str, chunks, depth: int, compute) -> float:
    from mmlspark_tpu.io.feed import DeviceFeed, FeedTelemetry

    tel = FeedTelemetry()
    if strategy == "sharded":
        import jax

        from mmlspark_tpu.parallel.mesh import batch_sharding, make_mesh

        mesh = make_mesh()
        feed = DeviceFeed(mesh=mesh, depth=depth, telemetry=tel,
                          shard_strategy="sharded")
        t0 = time.perf_counter()
        outs = [compute(feed.put(c, batch_sharding(mesh, c.ndim)))
                for c, _n in chunks]
        jax.block_until_ready(outs)
        return time.perf_counter() - t0
    if strategy == "compressed":
        import jax

        from mmlspark_tpu.ops.wire_codec import rle_encode

        feed = DeviceFeed(depth=depth, telemetry=tel,
                          shard_strategy="compressed")
        t0 = time.perf_counter()
        outs = [compute(feed.put_group([rle_encode(c)])[0])
                for c, _n in chunks]
        jax.block_until_ready(outs)
        return time.perf_counter() - t0
    feed = DeviceFeed(depth=depth, coalesce=8, telemetry=tel,
                      shard_strategy="coalesced")
    t0 = time.perf_counter()
    feed.run(iter(chunks), compute, greedy=False)
    return time.perf_counter() - t0


def _write_winner(path: str, winner: dict) -> None:
    """tmp + fsync + rename: a torn config file must never exist — a
    half-written JSON would silently un-tune every feed that reads it."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(winner, f, indent=2)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--images", type=int, default=256)
    ap.add_argument("--side", type=int, default=224)
    ap.add_argument("--chunk-sizes", default="16,32,64",
                    help="comma list of images per chunk to sweep")
    ap.add_argument("--depths", default="1,2,4",
                    help="comma list of pipeline depths to sweep")
    ap.add_argument("--strategies", default="coalesced,sharded",
                    help="comma subset of coalesced,sharded,compressed")
    ap.add_argument("--trials", type=int, default=2,
                    help="timed repeats per combo (best-of)")
    ap.add_argument("--out", default=os.path.join(ROOT, "FEED_TUNED.json"),
                    help="winner config path ('' to skip writing)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    chunk_sizes = [int(x) for x in args.chunk_sizes.split(",") if x]
    depths = [int(x) for x in args.depths.split(",") if x]
    strategies = [s for s in args.strategies.split(",") if s]
    dp = len(jax.devices())

    @jax.jit
    def compute(x):
        return jnp.asarray(x, jnp.float32).mean(axis=(1, 2, 3))

    rng = np.random.default_rng(0)
    rows = []
    for chunk in chunk_sizes:
        if "sharded" in strategies:
            chunk = max(dp, (chunk // dp) * dp)  # shardable batch
        chunks = _make_chunks(args.images, chunk, args.side, rng)
        images = sum(n for _c, n in chunks)
        for strategy in strategies:
            for depth in depths:
                # warm (compile) outside the timed trials
                _wall(strategy, chunks[:1], depth, compute)
                best = min(_wall(strategy, chunks, depth, compute)
                           for _ in range(max(1, args.trials)))
                rows.append({"chunk": chunk, "depth": depth,
                             "strategy": strategy,
                             "wall_s": round(best, 4),
                             "ips": round(images / best, 1)})
    rows.sort(key=lambda r: r["wall_s"])
    best = rows[0]
    winner = {"chunk": best["chunk"], "depth": best["depth"],
              "coalesce": 8, "strategy": best["strategy"],
              "platform": jax.devices()[0].platform, "devices": dp,
              "tuned_ips": best["ips"]}
    if args.out:
        _write_winner(args.out, winner)
    print(json.dumps({"winner": winner, "sweep": rows,
                      "out": args.out or None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
