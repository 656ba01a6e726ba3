"""DeviceFeed engine: coalescing correctness, ring/donation reuse,
telemetry accuracy, and the transfer-call microbench — all on the CPU
backend (the engine is backend-agnostic; what it owes every backend is
byte-exact round-trips and honest counters, and those are assertable
without a chip)."""
import numpy as np
import pytest

from mmlspark_tpu.io.feed import (
    FEED_TELEMETRY,
    DeviceFeed,
    FeedTelemetry,
    default_depth,
)


def _chunks(rng, n, shape, dtype=np.uint8):
    out = []
    for _ in range(n):
        if np.issubdtype(dtype, np.integer):
            out.append(rng.integers(0, 250, shape).astype(dtype))
        else:
            out.append(rng.standard_normal(shape).astype(dtype))
    return out


# ---- coalescing correctness ------------------------------------------------

def test_put_group_mixed_shape_round_trip(rng):
    """The byte-packed wire format must be lossless across shapes AND
    dtypes: offsets align, the on-device unpack slices/bitcasts each
    array back out exactly."""
    feed = DeviceFeed(telemetry=FeedTelemetry())
    arrays = [
        rng.integers(0, 255, (4, 7, 3)).astype(np.uint8),
        rng.integers(-100, 100, (5,)).astype(np.int32),
        rng.standard_normal((3, 9)).astype(np.float32),
        rng.standard_normal((2, 2, 2)).astype(np.float16),
    ]
    outs = feed.put_group(arrays)
    assert len(outs) == len(arrays)
    for a, d in zip(arrays, outs):
        got = np.asarray(d)
        assert got.dtype == a.dtype and got.shape == a.shape
        np.testing.assert_array_equal(got, a)


def test_run_packed_mixed_shapes_equal_per_chunk(rng):
    """Packed mixed-shape round-trip equals per-chunk results: the same
    compute over chunks fed one-at-a-time (no coalescing possible) and
    over the coalesced packed wire must produce identical outputs."""
    import jax.numpy as jnp

    chunks = [
        (rng.integers(0, 255, (4, 6, 6, 3)).astype(np.uint8), 4),
        (rng.integers(0, 255, (4, 8, 8, 3)).astype(np.uint8), 3),
        (rng.standard_normal((2, 5)).astype(np.float32), 2),
        (rng.integers(0, 255, (4, 6, 6, 3)).astype(np.uint8), 2),
    ]

    def compute(x):
        return jnp.asarray(x, jnp.float32) * 2.0 + 1.0

    naive = [np.asarray(compute(c))[:n] for c, n in chunks]
    tel = FeedTelemetry()
    got = DeviceFeed(depth=2, coalesce=4, telemetry=tel).run(
        iter(chunks), compute, greedy=False)
    assert len(got) == len(naive)
    for g, ref in zip(got, naive):
        np.testing.assert_array_equal(g, ref)
    # all four chunks rode coalesced transfers (mixed shapes byte-pack
    # on the default single target device)
    c = tel.snapshot()
    assert c["chunks_fed"] == 4
    assert c["transfer_calls"] < 4


def test_run_same_shape_chunks_coalesce_and_match(rng):
    """Same-shape chunks stack into [k, bs, ...] transfers; outputs must
    stay per-chunk exact and in feed order."""
    import jax.numpy as jnp

    chunks = [(c, c.shape[0] - (i % 2))
              for i, c in enumerate(_chunks(rng, 8, (4, 5, 5, 3)))]

    def compute(x):
        return jnp.asarray(x, jnp.float32).sum(axis=(1, 2)) * 0.5

    naive = [np.asarray(compute(c))[:n] for c, n in chunks]
    tel = FeedTelemetry()
    got = DeviceFeed(depth=2, coalesce=4, telemetry=tel).run(
        iter(chunks), compute, greedy=False)
    for g, ref in zip(got, naive):
        np.testing.assert_array_equal(g, ref)
    c = tel.snapshot()
    assert c["chunks_fed"] == 8
    assert c["coalesced_chunks"] == 8
    assert c["transfer_calls"] == 2  # 8 chunks / coalesce=4


# ---- ring / donation reuse -------------------------------------------------

@pytest.mark.parametrize("depth", [2, 4])
def test_ring_reuse_under_depth(rng, depth):
    """The staging ring holds depth+1 slots per wire shape and reuses
    them round-robin across many groups.  Correctness under reuse IS the
    donation/fencing property: a slot rewritten before its group drained
    (or a donated packed buffer read after the unpack consumed it) would
    corrupt later chunks' bytes."""
    import jax.numpy as jnp

    chunks = [(c, c.shape[0]) for c in _chunks(rng, 24, (4, 16, 3))]

    def compute(x):
        return jnp.asarray(x, jnp.int32) + 1

    naive = [np.asarray(compute(c))[:n] for c, n in chunks]
    feed = DeviceFeed(depth=depth, coalesce=2, telemetry=FeedTelemetry())
    got = feed.run(iter(chunks), compute, greedy=False)
    for g, ref in zip(got, naive):
        np.testing.assert_array_equal(g, ref)
    # 24 chunks / coalesce=2 = 12 groups, far more than the ring size:
    # every slot was rewritten several times
    rings = list(feed._rings.values())
    assert len(rings) == 1
    assert len(rings[0]) == depth + 1
    assert feed.telemetry.snapshot()["groups"] == 12


def test_ring_reuse_across_put_group_calls(rng):
    """put_group's fence must block slot rewrite until the previous
    group's unpacked outputs exist on device — byte equality across many
    reuses of the same wire-shape slot proves it."""
    feed = DeviceFeed(depth=2, telemetry=FeedTelemetry())
    for _ in range(10):
        a = rng.integers(0, 255, (16, 16)).astype(np.uint8)
        b = rng.standard_normal((8,)).astype(np.float32)
        da, db = feed.put_group([a, b])
        np.testing.assert_array_equal(np.asarray(da), a)
        np.testing.assert_array_equal(np.asarray(db), b)
    ring = feed._rings[next(iter(feed._rings))]
    assert len(ring) == feed.depth + 1


# ---- telemetry -------------------------------------------------------------

def test_telemetry_counter_accuracy(rng):
    tel = FeedTelemetry()
    feed = DeviceFeed(depth=2, telemetry=tel)
    a = rng.integers(0, 255, (4, 8, 8, 3)).astype(np.uint8)
    feed.put(a, block=True)
    c = tel.snapshot()
    assert c["bytes_moved"] == a.nbytes
    assert c["transfer_calls"] == 1 and c["chunks_fed"] == 1
    assert c["transfer_s"] > 0

    # a packed group moves the ALIGNED wire total in one call
    b = rng.standard_normal((10,)).astype(np.float32)
    feed.put_group([a, b])
    c2 = tel.snapshot()
    assert c2["transfer_calls"] == 2
    assert c2["coalesced_chunks"] == 2 and c2["chunks_fed"] == 3
    wire = c2["bytes_moved"] - a.nbytes
    assert wire >= a.nbytes + b.nbytes          # both payloads moved...
    assert wire <= a.nbytes + b.nbytes + 2 * 128  # ...plus alignment only


def test_telemetry_summarize_fields(rng):
    import jax.numpy as jnp

    tel = FeedTelemetry()
    chunks = [(c, 4) for c in _chunks(rng, 8, (4, 8, 8, 3))]
    DeviceFeed(depth=2, coalesce=4, telemetry=tel).run(
        iter(chunks), lambda x: jnp.asarray(x, jnp.float32))
    s = FeedTelemetry.summarize(tel.snapshot())
    assert s["chunks_fed"] == 8
    assert s["feed_bytes"] >= sum(c.nbytes for c, _n in chunks)
    assert s["transfer_calls"] >= 1
    assert s["h2d_gbps"] is None or s["h2d_gbps"] > 0
    assert s["overlap_frac"] is not None and 0.0 <= s["overlap_frac"] <= 1.0
    assert s["stall_s"] >= 0.0


def test_default_depth_env_override(monkeypatch):
    monkeypatch.delenv("MMLSPARK_FEED_DEPTH", raising=False)
    assert default_depth() == 2
    monkeypatch.setenv("MMLSPARK_FEED_DEPTH", "4")
    assert default_depth() == 4
    monkeypatch.setenv("MMLSPARK_FEED_DEPTH", "bogus")
    assert default_depth() == 2
    assert DeviceFeed(depth=0).depth == 1  # floor: a 0-depth feed stalls


# ---- stream (train-loop consumer shape) ------------------------------------

def test_stream_round_trip_in_order(rng):
    items = [(rng.standard_normal((6, 3)).astype(np.float32),
              rng.integers(0, 9, (6,)).astype(np.int32))
             for _ in range(7)]
    feed = DeviceFeed(depth=2, telemetry=FeedTelemetry())
    out = list(feed.stream(iter(items)))
    assert len(out) == 7
    for (hx, hy), (dx, dy) in zip(items, out):
        np.testing.assert_array_equal(np.asarray(dx), hx)
        np.testing.assert_array_equal(np.asarray(dy), hy)


# ---- the microbench acceptance bar -----------------------------------------

def test_coalesced_feed_beats_naive_on_transfer_calls(rng):
    """256 images in 16 chunks: the naive per-chunk feed pays 16
    device_put round trips; the coalesced depth-2 engine must pay <= 4
    (>= 4x fewer) while producing identical results.  Structural — call
    counts, not wall clock — so it cannot flake on a loaded host."""
    import jax.numpy as jnp

    chunks = [(c, 16) for c in _chunks(rng, 16, (16, 32, 32, 3))]
    assert sum(c.shape[0] for c, _n in chunks) == 256

    def compute(x):
        return jnp.asarray(x, jnp.float32).mean(axis=(1, 2, 3))

    naive_calls = len(chunks)  # one device_put per chunk, by construction
    naive = [np.asarray(compute(c))[:n] for c, n in chunks]

    tel = FeedTelemetry()
    got = DeviceFeed(depth=2, coalesce=8, telemetry=tel).run(
        iter(chunks), compute, greedy=False)
    for g, ref in zip(got, naive):
        np.testing.assert_array_equal(g, ref)
    calls = tel.snapshot()["transfer_calls"]
    assert calls * 4 <= naive_calls, (
        f"coalesced feed used {calls} transfer calls vs naive "
        f"{naive_calls} — less than the 4x amortization bar")


def test_process_telemetry_sink_is_shared():
    """Consumers default to the process-wide sink."""
    before = FEED_TELEMETRY.snapshot()
    DeviceFeed().put(np.zeros((2, 2), np.uint8))
    d = FEED_TELEMETRY.delta(before)
    assert d["transfer_calls"] == 1 and d["bytes_moved"] == 4


# ---- the autotuner config and strategy resolution --------------------------

def _clear_tuned_cache():
    from mmlspark_tpu.io import feed as feed_mod

    with feed_mod._TUNED_LOCK:
        feed_mod._TUNED_CACHE.clear()


def test_tuned_config_adopted_by_default_knobs(tmp_path, monkeypatch):
    """A feed_tune winner pointed at by MMLSPARK_FEED_TUNED fills every
    knob the caller left at None; explicit arguments still win."""
    import json

    from mmlspark_tpu.io.feed import load_tuned

    cfg = tmp_path / "tuned.json"
    cfg.write_text(json.dumps({"depth": 3, "coalesce": 6,
                               "strategy": "coalesced"}))
    monkeypatch.setenv("MMLSPARK_FEED_TUNED", str(cfg))
    monkeypatch.delenv("MMLSPARK_FEED_DEPTH", raising=False)
    _clear_tuned_cache()
    assert load_tuned()["depth"] == 3
    feed = DeviceFeed()
    assert feed.depth == 3 and feed.coalesce == 6
    assert feed.shard_strategy == "coalesced"
    explicit = DeviceFeed(depth=1, coalesce=2, shard_strategy="auto")
    assert explicit.depth == 1 and explicit.coalesce == 2
    assert explicit.shard_strategy == "auto"
    _clear_tuned_cache()


def test_tuned_config_corrupt_file_is_empty_not_fatal(tmp_path,
                                                      monkeypatch):
    """A torn/corrupt tuned file must un-tune, never crash: tuning is
    an optimization, not a dependency."""
    from mmlspark_tpu.io.feed import load_tuned

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv("MMLSPARK_FEED_TUNED", str(bad))
    _clear_tuned_cache()
    assert load_tuned() == {}
    feed = DeviceFeed()  # defaults, no exception
    assert feed.depth >= 1
    _clear_tuned_cache()


def test_shard_strategy_env_beats_tuned(tmp_path, monkeypatch):
    import json

    cfg = tmp_path / "tuned.json"
    cfg.write_text(json.dumps({"strategy": "sharded"}))
    monkeypatch.setenv("MMLSPARK_FEED_TUNED", str(cfg))
    monkeypatch.setenv("MMLSPARK_FEED_SHARD", "coalesced")
    _clear_tuned_cache()
    assert DeviceFeed().shard_strategy == "coalesced"
    _clear_tuned_cache()


def test_shard_strategy_rejects_unknown():
    with pytest.raises(ValueError, match="shard_strategy"):
        DeviceFeed(shard_strategy="turbo")


def test_feed_tune_sweep_writes_winner(tmp_path):
    """The autotuner end to end on a tiny sweep: a winner JSON lands
    atomically and carries the keys DeviceFeed consults."""
    import json

    from tools.feed_tune import main as tune_main

    out = tmp_path / "FEED_TUNED.json"
    rc = tune_main(["--images", "8", "--side", "16", "--chunk-sizes",
                    "4", "--depths", "1", "--strategies", "coalesced",
                    "--trials", "1", "--out", str(out)])
    assert rc == 0
    winner = json.loads(out.read_text())
    assert winner["strategy"] == "coalesced"
    assert winner["depth"] == 1 and winner["chunk"] == 4
    assert {"coalesce", "platform", "devices"} <= set(winner)
