"""DeviceFeed: the unified host->device transfer engine.

Every consumer that moves bulk data onto the chip — ImageFeaturizer's
streaming byte path, TPUModel's executor feed, DeepVisionClassifier's
train loop, `fit_epochs`, and the serving ContinuousBatcher's per-tick
uploads — routes its transfers through this module.  The reference
system solved the same problem on Spark by consolidating small
partitions into large batched transfers before they hit the native
engine (MiniBatchBase/FlattenBatch + PartitionConsolidator); here the
fixed per-transfer cost of a `device_put` is amortized the same way,
JAX-first:

  * **Transfer coalescing.**  Consecutive same-shape chunks pack into
    one `[k, bs, ...]` staging buffer and ride ONE `device_put`; mixed
    shape/dtype chunks byte-pack into a single uint8 wire buffer with a
    byte-offset header and are sliced/bitcast back apart ON DEVICE.
    Coalescing is adaptive: the engine drains whatever the producer has
    ready and never waits for a fuller pack (`greedy=True`), so a
    decode-bound pipeline degrades to singleton transfers with zero
    added latency while a compute/transfer-bound one packs to the cap.
  * **uint8 wire format.**  The engine is dtype-preserving: image paths
    feed uint8 end-to-end (4x fewer bytes than f32) and the consumer's
    jitted program does the cast/normalize on device (ImagePreprocess).
  * **Ring of staging buffers, bounded depth.**  Host packing buffers
    come from a per-wire-shape ring of `depth + 1` slots reused round
    robin — no per-batch allocation; a slot is rewritten only after the
    group that used it has fully drained (device_put can alias host
    memory zero-copy on the CPU backend, so reuse MUST be fenced on the
    consumer side).  The packed device buffer is donated to the unpack
    program, so its HBM is released/aliased the moment the chunks are
    split apart.  `depth` packed transfers are in flight at once
    (default 2, tunable).
  * **Telemetry.**  Bytes moved, transfer calls/seconds, per-stage
    stall seconds, and wall time accumulate in `FEED_TELEMETRY`;
    `FeedTelemetry.summarize` derives `overlap_frac`/`stall_s`/
    `h2d_gbps` from a delta.  See docs/performance.md ("The h2d feed").
  * **Fault tolerance.**  Every `device_put` sits behind the
    `feed.device_put` fault point with a bounded retry
    (`transfer_retries`, tiny backoff — a transient link hiccup costs
    microseconds, not a failed batch).  Since the graftflow unification
    the retry ladder is a `core.flow.StagePolicy` (the same
    retry-then-degrade shape every flow stage can wear), with backoff
    sleeps through the injectable clock.  A PACKED transfer that fails
    all its retries **degrades the engine**: the group falls back to
    plain per-chunk puts and the instance stays on the safe unpipelined
    path (no coalescing, no in-flight window) for the rest of its life —
    correctness first, the packed fast path is an optimization.  Retries
    and degradations count into `core.telemetry` ("feed.transfer_retry",
    "feed.degraded"); see docs/robustness.md (degradation ladder).
  * **A registered flow stage.**  `DeviceFeed.stage()` exposes the h2d
    hop as an `H2DStage` for credit-bounded FlowGraphs
    (decode -> assemble -> h2d), with the `flow.h2d` fault point and
    declared `flow.*.h2d` telemetry (lint rule G405).
  * **Sharded direct-to-chip transfers.**  On a multi-device mesh a
    single monolithic `device_put` serializes the whole batch through
    one transfer stream; with `shard_strategy="auto"` (the default) the
    feed hands evenly-divisible sharded puts to `io.shard_put.
    ShardEngine` — one concurrent per-device transfer per addressable
    shard, staged through pre-pinned size-bucketed buffers, assembled
    zero-copy with `make_array_from_single_device_arrays`.  Each shard
    rides the `feed.shard_put` fault point behind its own StagePolicy
    rung; a shard group that exhausts its retries falls back to the
    coalesced single-put path and the engine stays there
    (`shard_degraded`, one rung above the PR-2 ladder).  Non-divisible
    batches fall back per call (`h2d_path="fallback"` in bench).
  * **Compressed wire.**  `put_group` accepts `ops.wire_codec.
    RLEPayload` items (still-encoded byte-RLE chunks + a cumulative
    length table): the wire carries values+ends only — 2-20x fewer
    bytes on runnable pixel data — and the chunk is re-expanded ON
    DEVICE (Pallas page-walk kernel on TPU, `jnp.repeat` everywhere
    else; transparent fallback rung).  Tune all three knobs with
    `tools/feed_tune.py`; the winner persists via MMLSPARK_FEED_TUNED.
"""
from __future__ import annotations

import json
import os
import queue
import threading
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..core import telemetry as core_telemetry
from ..core.flow import Stage, StagePolicy
from ..utils.faults import fault_point
from ..utils.sync import make_lock

__all__ = ["DeviceFeed", "H2DStage", "FeedTelemetry", "FEED_TELEMETRY",
           "default_depth", "FeedSource", "FEED_END", "FEED_FAULT_POINTS",
           "load_tuned", "host_local_feed"]

# every fault point the feed engine can cross — chaos_soak enumerates
# this alongside flow_fault_points() so its full-coverage plan can never
# go stale when a transfer path gains a new point
FEED_FAULT_POINTS = ("feed.device_put", "feed.shard_put")

_ALIGN = 128  # byte-pack offset alignment (covers every feed dtype's itemsize)

# terminal marker a FeedSource returns once its stream is exhausted
FEED_END = object()


class FeedSource:
    """Protocol for multi-producer chunk sources driving `DeviceFeed.run`.

    PR 2's `run()` hid exactly one prefetch thread behind a plain
    iterator — decode AND assembly serialized on it.  A FeedSource owns
    its production concurrency (the HostPipeline adapter in
    io/pipeline.py runs N decode workers) and the feed engine just pulls
    ready chunks:

      * ``start()``    — begin producing (called once by `run`).
      * ``get()``      — block until the next (chunk, n_valid) item, or
                         return ``FEED_END`` when the stream is done
                         (terminal: keep returning it).
      * ``get_nowait()``— same, but raise ``queue.Empty`` instead of
                         blocking when nothing is ready yet.
      * ``error()``    — the producer-side exception to re-raise after
                         in-flight groups drain, or None.

    Plain iterables passed to `run()` are wrapped in `_IterSource`,
    which reproduces the old single-prefetch-thread behavior exactly —
    the original signature keeps working."""

    def start(self) -> None:
        raise NotImplementedError

    def get(self):
        raise NotImplementedError

    def get_nowait(self):
        raise NotImplementedError

    def error(self) -> Optional[BaseException]:
        return None


class _IterSource(FeedSource):
    """The PR-2 shape: one daemon thread drains `chunk_iter` into a
    bounded queue (decode/assembly overlap device compute; backpressure
    via maxsize)."""

    def __init__(self, chunk_iter: Iterable, maxsize: int):
        self._it = chunk_iter
        self._q: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self._err: List[BaseException] = []

    def start(self):
        threading.Thread(target=self._produce, daemon=True,
                         name="device-feed-producer").start()

    def _produce(self):
        try:
            for item in self._it:
                self._q.put(item)
                core_telemetry.gauge("io.feed.queue.depth").set(
                    self._q.qsize())
        except BaseException as e:  # noqa: BLE001 — forwarded to consumer
            self._err.append(e)
        finally:
            self._q.put(FEED_END)

    def _terminal(self, item):
        if item is FEED_END:
            self._q.put(FEED_END)  # stay terminal for later gets
        return item

    def get(self):
        return self._terminal(self._q.get())

    def get_nowait(self):
        return self._terminal(self._q.get_nowait())

    def error(self) -> Optional[BaseException]:
        return self._err[0] if self._err else None


def default_depth() -> int:
    """Pipeline depth: packed transfers in flight (MMLSPARK_FEED_DEPTH
    overrides for experiments; the knob every consumer inherits)."""
    try:
        return max(1, int(os.environ.get("MMLSPARK_FEED_DEPTH", "2")))
    except ValueError:
        return 2


_TUNED_LOCK = make_lock("io.feed.tuned")
_TUNED_CACHE: Dict[str, Dict[str, Any]] = {}  #: guarded-by _TUNED_LOCK


def load_tuned() -> Dict[str, Any]:
    """The autotuned feed config (`tools/feed_tune.py` winner), read from
    the MMLSPARK_FEED_TUNED path once per process.  Keys: `depth`,
    `coalesce`, `strategy` — DeviceFeed consults them for any knob the
    caller left at None.  A missing/corrupt file is an empty config, not
    an error: tuning is an optimization, never a dependency."""
    path = os.environ.get("MMLSPARK_FEED_TUNED", "")
    if not path:
        return {}
    with _TUNED_LOCK:
        cfg = _TUNED_CACHE.get(path)
        if cfg is None:
            try:
                with open(path, encoding="utf-8") as f:
                    doc = json.load(f)
                cfg = doc if isinstance(doc, dict) else {}
            except (OSError, ValueError):
                cfg = {}
            _TUNED_CACHE[path] = cfg
        return cfg


def host_local_feed(model: int = 1, seq: int = 1, **kwargs) -> "DeviceFeed":
    """A DeviceFeed over THIS host's addressable chips.  On a
    multi-process mesh every host feeds only the devices it can address
    (``jax.local_devices()``), each process running its own transfer
    rings and shard_put pool against its own chips — the per-host half
    of the elastic runtime (parallel/distributed.py); the sharded path
    underneath is already per-host by construction
    (`addressable_shard_layout` maps addressable shards only).
    Single-process this is exactly ``DeviceFeed(mesh=make_mesh())``."""
    import jax

    from ..parallel.mesh import make_mesh

    mesh = make_mesh(model=model, seq=seq, devices=jax.local_devices())
    return DeviceFeed(mesh=mesh, **kwargs)


class FeedTelemetry:
    """Thread-safe monotonic counters for the feed engine.

    `transfer_s` is the wall time the feeding thread spends inside
    `device_put` dispatch — through a synchronous transport (the CPU
    backend) that IS the host-visible transfer cost; a fully async
    transport under-reports, which only makes the
    derived `overlap_frac` conservative in the other direction (it can
    report transfers as hidden when they were simply invisible).
    """

    _FIELDS = ("bytes_moved", "transfer_calls", "transfer_s", "chunks_fed",
               "coalesced_chunks", "groups", "stall_decode_s",
               "stall_drain_s", "compute_s", "wall_s",
               # the sharded direct-to-chip path (io/shard_put.py)
               "sharded_groups", "fallback_groups", "shard_puts",
               "shard_bytes", "shard_wall_s", "shard_put_s",
               # the compressed wire path (ops/wire_codec.py)
               "compressed_groups", "wire_bytes_raw", "wire_bytes_sent")
    # high-water marks, not sums (note_max; delta reports the mark itself)
    _MAX_FIELDS = ("transfer_concurrency",)

    def __init__(self):
        self._lock = make_lock("io.feed.telemetry")
        self._c: Dict[str, float] = {f: 0.0 for f in self._FIELDS}
        self._c.update({f: 0.0 for f in self._MAX_FIELDS})

    def add(self, **kw: float):
        with self._lock:
            for k, v in kw.items():
                self._c[k] += v

    def note_max(self, **kw: float):
        """Raise high-water fields (`_MAX_FIELDS`) to at least `kw`."""
        with self._lock:
            for k, v in kw.items():
                if v > self._c[k]:
                    self._c[k] = v

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._c)

    def transfer_seconds(self) -> float:
        """Cumulative host-visible H2D seconds: `device_put` dispatch
        plus the sharded per-shard puts.  The goodput ledger diffs this
        around a step's `put_group` to attribute the step's `h2d`
        segment (docs/observability.md, "The goodput plane")."""
        with self._lock:
            return self._c["transfer_s"] + self._c["shard_put_s"]

    def delta(self, since: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: (now[k] if k in self._MAX_FIELDS
                    else now[k] - since.get(k, 0.0)) for k in now}

    @staticmethod
    def summarize(d: Dict[str, float]) -> Dict[str, Any]:
        """Derived metrics from a counter delta.

        overlap_frac: fraction of feed wall time NOT spent blocked on
        host-side feeding (decode stalls + transfer dispatch).  1.0
        means every transfer hid under device compute; a feed bound by
        transfer bandwidth collapses toward 0.
        """
        wall = d.get("wall_s", 0.0)
        stall = d.get("stall_decode_s", 0.0) + d.get("stall_drain_s", 0.0)
        blocked = d.get("stall_decode_s", 0.0) + d.get("transfer_s", 0.0)
        out = {
            "feed_bytes": int(d.get("bytes_moved", 0)),
            "transfer_calls": int(d.get("transfer_calls", 0)),
            "chunks_fed": int(d.get("chunks_fed", 0)),
            "stall_s": round(stall, 4),
            "overlap_frac": (round(max(0.0, min(1.0, 1.0 - blocked / wall)), 4)
                             if wall > 0 else None),
            "h2d_gbps": (round(d["bytes_moved"] / d["transfer_s"] / 1e9, 4)
                         if d.get("transfer_s", 0) > 0 else None),
        }
        # the sharded-path breakdown (ISSUE 14): which transfer path the
        # window actually took, its per-shard bandwidth, and the transfer
        # pool's concurrency high-water
        sharded = int(d.get("sharded_groups", 0))
        fallback = int(d.get("fallback_groups", 0))
        if sharded > 0 and sharded >= fallback:
            out["h2d_path"] = "sharded"
        elif fallback > 0:
            out["h2d_path"] = "fallback"
        else:
            out["h2d_path"] = "coalesced"
        out["shard_gbps"] = (
            round(d["shard_bytes"] / d["shard_wall_s"] / 1e9, 4)
            if d.get("shard_wall_s", 0) > 0 else None)
        out["transfer_concurrency"] = (
            int(d.get("transfer_concurrency", 0)) or None)
        sent = d.get("wire_bytes_sent", 0.0)
        out["wire_ratio"] = (round(d.get("wire_bytes_raw", 0.0) / sent, 3)
                             if sent > 0 else None)
        # mirror the derived numbers onto the registry so /metrics and
        # export_snapshot() carry the latest feed summary
        core_telemetry.gauge("io.feed.stall_s").set(out["stall_s"])
        if out["overlap_frac"] is not None:
            core_telemetry.gauge("io.feed.overlap_frac").set(
                out["overlap_frac"])
        if out["wire_ratio"] is not None:
            core_telemetry.gauge("io.feed.shard.wire_ratio").set(
                out["wire_ratio"])
        return out


# process-wide default sink: the benchmark's reducers and tests read
# deltas off this
FEED_TELEMETRY = FeedTelemetry()


def _first_call(fn, arg):
    """First (compiling) invocation of an unpack program: the donated
    staging buffer's split outputs are smaller than the input, so XLA can
    never alias them and warns — the donation is still wanted (it frees
    the packed HBM at execution instead of at Python ref-drop), so the
    expected warning is silenced for exactly this call."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return fn(arg)


class _RingSlot:
    __slots__ = ("buf", "busy", "fence")

    def __init__(self):
        self.buf: Optional[np.ndarray] = None
        self.busy = False
        self.fence: Any = None  # device values to block on before reuse


class DeviceFeed:
    """One host->device feed: coalescing + ring staging + depth pipelining.

    mesh=None feeds the default device uncommitted (the serving shape);
    with a mesh, `run()` feeds batch-sharded chunks over the 'data' axis.
    Instances are cheap (rings allocate lazily); consumers create one per
    transform/fit/loop and share the process-wide telemetry sink.
    """

    def __init__(self, mesh=None, depth: Optional[int] = None,
                 coalesce: Optional[int] = None,
                 coalesce_bytes: int = 64 << 20,
                 telemetry: Optional[FeedTelemetry] = None,
                 transfer_retries: int = 3,
                 shard_strategy: Optional[str] = None):
        tuned = load_tuned()
        self.mesh = mesh
        if depth is None:
            depth = tuned.get("depth") or default_depth()
        self.depth = max(1, int(depth))
        if coalesce is None:
            coalesce = tuned.get("coalesce") or 4
        self.coalesce = max(1, int(coalesce))
        self.coalesce_bytes = int(coalesce_bytes)
        self.telemetry = telemetry if telemetry is not None else FEED_TELEMETRY
        self.transfer_retries = max(1, int(transfer_retries))
        # sharded-path strategy: explicit arg > env > autotuned > auto.
        # "auto"/"sharded" route evenly-divisible multi-device puts
        # through ShardEngine; "coalesced" pins the PR-2 single-put path;
        # "compressed" additionally advertises the RLE wire to consumers
        # that ask (`prefers_compressed`).
        if shard_strategy is None:
            shard_strategy = (os.environ.get("MMLSPARK_FEED_SHARD")
                              or tuned.get("strategy") or "auto")
        if shard_strategy not in ("auto", "sharded", "coalesced",
                                  "compressed"):
            raise ValueError(f"unknown shard_strategy {shard_strategy!r}")
        self.shard_strategy = shard_strategy
        # a shard group that exhausted its retries flips this: the feed
        # stays on the coalesced single-put path for the rest of its
        # life (same sticky shape as `degraded`, one rung above it)
        self.shard_degraded = False
        self._shard_engine = None
        self._shard_policy = StagePolicy(retries=self.transfer_retries,
                                         backoff_s=0.001, backoff_cap_s=0.05,
                                         retry_counter="feed.shard_retry")
        # the retry rungs of the degradation ladder, as the shared
        # StagePolicy shape (core/flow.py); the terminal degrade rung
        # stays at the call sites, which know whether the failed put was
        # packed (degrade the engine) or already a singleton (raise)
        self._put_policy = StagePolicy(retries=self.transfer_retries,
                                       backoff_s=0.001, backoff_cap_s=0.05,
                                       retry_counter="feed.transfer_retry")
        # a packed transfer that failed all its retries flips this: the
        # instance stays on the safe per-chunk unpipelined path for the
        # rest of its life (instances are per-transform/fit, so the blast
        # radius of a flaky link is one consumer, not the process)
        self.degraded = False
        self._rings: Dict[Any, List[_RingSlot]] = {}
        self._ring_pos: Dict[Any, int] = {}
        self._unpackers: Dict[Any, Callable] = {}
        # materialize the degraded-engines gauge at 0 so a /metrics scrape
        # sees the series before (and whether or not) anything degrades
        core_telemetry.gauge("io.feed.degraded_engines")

    def _obs_transfer(self, nbytes: float, dt: float, chunks: int) -> None:
        """Per-transfer registry instrumentation: latency + size
        histograms always; a `feed.transfer` child span when the calling
        thread is inside a trace (a served request's batch tick), so the
        device upload shows up in that request's `/trace/<id>` tree."""
        core_telemetry.histogram("io.feed.transfer.latency").observe(dt)
        core_telemetry.histogram(
            "io.feed.transfer.bytes",
            boundaries=core_telemetry.BYTE_BUCKETS).observe(nbytes)
        ctx = core_telemetry.current_context()
        if ctx is not None:
            core_telemetry.record_span("feed.transfer", ctx, dt,
                                       bytes=int(nbytes), chunks=chunks)

    # ---- guarded transfer ----------------------------------------------
    def _device_put(self, arr, sharding=None):
        """The one raw `jax.device_put` in the engine: named fault point +
        bounded retry with a tiny backoff (a transient link error costs
        microseconds, not the batch), run as a `StagePolicy` ladder."""
        import jax

        def attempt(a):
            fault_point("feed.device_put")
            # the transfer span itself is recorded after the fact
            # via record_span, which can't annotate
            with core_telemetry.device_annotation("feed.transfer"):
                return (jax.device_put(a, sharding)
                        if sharding is not None
                        else jax.device_put(a))

        return self._put_policy.run(attempt, arr)

    def _degrade(self, why: str):
        if not self.degraded:
            self.degraded = True
            core_telemetry.incr("feed.degraded")
            core_telemetry.gauge("io.feed.degraded_engines").inc()
            warnings.warn(f"DeviceFeed degraded to unpipelined transfers: {why}",
                          RuntimeWarning, stacklevel=3)

    # ---- the sharded direct-to-chip path (io/shard_put.py) -------------
    def _engine(self):
        if self._shard_engine is None:
            from .shard_put import ShardEngine

            # an explicit "sharded" strategy is a directive, not a hint:
            # drop the per-shard size floor so even small batches (tests,
            # the autotuner's sweeps) take the per-device path
            floor = 0 if self.shard_strategy == "sharded" else 1 << 12
            self._shard_engine = ShardEngine(policy=self._shard_policy,
                                             telemetry=self.telemetry,
                                             min_shard_bytes=floor)
        return self._shard_engine

    def _degrade_shard(self, why: str):
        """The shard rung of the ladder: sticky per-feed fall-back to the
        coalesced single-put path (which keeps ITS retry/degrade rungs)."""
        if not self.shard_degraded:
            self.shard_degraded = True
            core_telemetry.incr("feed.shard_degraded")
            warnings.warn(
                f"DeviceFeed sharded path degraded to coalesced: {why}",
                RuntimeWarning, stacklevel=3)

    def _try_sharded(self, arr: np.ndarray, sharding):
        """`arr` through the sharded engine, or None when this put is not
        eligible (strategy, degraded, uneven batch, single target) — the
        caller continues on the coalesced path.  Ineligibility of a
        genuinely multi-device put is counted as a fallback group
        (`fallback_groups`)."""
        from .shard_put import ShardTransferError

        if self.shard_degraded or self.shard_strategy == "coalesced":
            return None
        if sharding is None:
            return None
        from .shard_put import shard_layout

        eng = self._engine()
        layout = shard_layout(sharding, arr.shape)
        if layout is None or len(layout) <= 1:
            # uneven batch (or a single-target sharding): only the former
            # is a genuine fall-off of the sharded path
            try:
                multi = len(sharding.addressable_devices) > 1
            except (AttributeError, TypeError):
                multi = False
            if multi:
                self.telemetry.add(fallback_groups=1)
                core_telemetry.incr("io.feed.shard.fallback")
            return None
        if arr.nbytes // len(layout) < eng.min_shard_bytes:
            # below the per-shard floor the fixed per-put cost wins:
            # coalescing is the DELIBERATE choice here, not a fallback
            return None
        try:
            out = eng.put_sharded(arr, sharding, layout)
        except ShardTransferError as e:
            self._degrade_shard(f"shard put failed after retries: {e}")
            self.telemetry.add(fallback_groups=1)
            core_telemetry.incr("io.feed.shard.fallback")
            return None
        self.telemetry.add(chunks_fed=1, groups=1)
        return out

    # ---- sharding helpers ----------------------------------------------
    def _dp(self) -> int:
        return self.mesh.shape["data"] if self.mesh is not None else 1

    def _chunk_sharding(self, ndim: int):
        if self.mesh is None:
            return None
        from ..parallel.mesh import batch_sharding

        return batch_sharding(self.mesh, ndim)

    def _packed_sharding(self, ndim: int):
        """Sharding for a [k, bs, ...] packed buffer: batch axis is dim 1."""
        if self.mesh is None:
            return None
        from ..parallel.mesh import batch_sharding

        return batch_sharding(self.mesh, ndim, batch_axis=1)

    # ---- single transfers ----------------------------------------------
    def put(self, arr, sharding=None, block: bool = False):
        """One counted `device_put`.  `block=True` waits for the transfer
        (bandwidth probes); otherwise dispatch is async like raw jax.
        Multi-device sharded puts that divide evenly ride the concurrent
        per-shard engine; everything else takes the coalesced path."""
        import jax

        arr = np.asarray(arr)
        out = self._try_sharded(arr, sharding)
        if out is not None:
            if block:
                jax.block_until_ready(out)
            return out
        t0 = time.perf_counter()
        out = self._device_put(arr, sharding)
        if block:
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self.telemetry.add(bytes_moved=arr.nbytes, transfer_calls=1,
                           transfer_s=dt, chunks_fed=1, groups=1)
        self._obs_transfer(arr.nbytes, dt, 1)
        return out

    def put_group(self, arrays: Sequence[np.ndarray], shardings=None,
                  sharded_multi: bool = False):
        """Several host arrays -> device in ONE transfer when profitable.

        Arrays byte-pack into a single uint8 wire buffer (offset header)
        and are sliced/bitcast apart on device — one fixed per-transfer
        cost instead of len(arrays).  On a multi-device mesh a replicated
        byte buffer would multiply wire bytes, so unless the caller opts
        in (`sharded_multi` for replicated consumers), packing engages
        only single-device and the call degrades to per-array puts.

        Items may also be `ops.wire_codec.RLEPayload` (still-encoded
        chunks): the group then rides the compressed wire — one packed
        transfer of values + cumulative length tables, re-expanded ON
        DEVICE (Pallas page-walk kernel on TPU, XLA repeat elsewhere).
        """
        import jax

        from ..ops.wire_codec import RLEPayload

        if arrays and all(isinstance(a, RLEPayload) for a in arrays):
            return self._put_compressed(list(arrays))
        arrays = [np.ascontiguousarray(a) for a in arrays]
        if shardings is None:
            shardings = [None] * len(arrays)
        if len(arrays) == 1:
            return (self.put(arrays[0], shardings[0]),)
        multi = jax.device_count() > 1
        if multi and not sharded_multi and any(s is not None for s in shardings):
            return tuple(self.put(a, s) for a, s in zip(arrays, shardings))
        if self.degraded:
            return tuple(self.put(a, s) for a, s in zip(arrays, shardings))

        layout = []
        off = 0
        for a in arrays:
            layout.append((off, a.shape, a.dtype.str))
            off += -(-a.nbytes // _ALIGN) * _ALIGN
        total = max(off, _ALIGN)
        slot = self._acquire_slot(("bytes", total), (total,), np.uint8)
        for a, (o, _s, _d) in zip(arrays, layout):
            slot.buf[o:o + a.nbytes] = a.reshape(-1).view(np.uint8)
        t0 = time.perf_counter()
        try:
            packed = self._device_put(slot.buf)
        except Exception as e:  # noqa: BLE001 — degrade, then the safe path
            self._degrade(f"packed put_group failed after retries: {e}")
            return tuple(self.put(a, s) for a, s in zip(arrays, shardings))
        dt = time.perf_counter() - t0
        self.telemetry.add(bytes_moved=total, transfer_calls=1,
                           transfer_s=dt,
                           chunks_fed=len(arrays), groups=1,
                           coalesced_chunks=len(arrays))
        self._obs_transfer(total, dt, len(arrays))
        outs = self._unpack_bytes(packed, tuple(layout), shardings)
        # the slot is rewritten only after these outputs exist on device
        slot.fence = outs
        return outs

    def _put_compressed(self, payloads):
        """RLE-encoded chunks over the compressed wire: values + ends
        tables byte-pack into ONE transfer (the same wire buffer and
        fault/retry ladder as `put_group`), then each chunk is decoded
        back to its raw bytes on device (`ops.wire_codec.decode_bytes`)
        and bitcast/reshaped into shape.  A transfer that exhausts its
        retries — or an already-degraded feed — decodes on the HOST and
        rides plain per-chunk puts: the fallback costs wire bytes, never
        correctness."""
        from ..ops import wire_codec

        def host_fallback():
            outs = []
            for p in payloads:
                outs.append(self.put(wire_codec.decode_host(p)))
            return tuple(outs)

        if self.degraded:
            return host_fallback()
        wire: List[np.ndarray] = []
        for p in payloads:
            wire.append(p.values)
            wire.append(p.ends)
        layout = []
        off = 0
        for a in wire:
            layout.append((off, a.shape, a.dtype.str))
            off += -(-a.nbytes // _ALIGN) * _ALIGN
        total = max(off, _ALIGN)
        slot = self._acquire_slot(("bytes", total), (total,), np.uint8)
        for a, (o, _s, _d) in zip(wire, layout):
            slot.buf[o:o + a.nbytes] = a.reshape(-1).view(np.uint8)
        t0 = time.perf_counter()
        try:
            packed = self._device_put(slot.buf)
        except Exception as e:  # noqa: BLE001 — degrade, then the safe path
            self._degrade(f"compressed wire transfer failed after retries: {e}")
            return host_fallback()
        dt = time.perf_counter() - t0
        raw_bytes = sum(p.nbytes_raw for p in payloads)
        self.telemetry.add(bytes_moved=total, transfer_calls=1,
                           transfer_s=dt, chunks_fed=len(payloads),
                           groups=1, coalesced_chunks=len(payloads),
                           compressed_groups=1, wire_bytes_raw=raw_bytes,
                           wire_bytes_sent=total)
        self._obs_transfer(total, dt, len(payloads))
        core_telemetry.incr("io.feed.shard.compressed_groups")
        parts = self._unpack_bytes(packed, tuple(layout), None)
        use_pallas = wire_codec.rle_kernel_ok()
        outs = []
        for i, p in enumerate(payloads):
            v, e = parts[2 * i], parts[2 * i + 1]
            raw = wire_codec.decode_bytes(v, e, p.first_run, p.n_pad,
                                          use_pallas)
            outs.append(self._finish_decoded(raw, p))
        outs = tuple(outs)
        slot.fence = outs
        return outs

    def _finish_decoded(self, raw, payload):
        """Decoded uint8[n_pad] -> the chunk's dtype/shape on device; one
        cached jitted program per (n_pad, nbytes, dtype, shape)."""
        import jax

        key = ("rle", payload.n_pad, payload.nbytes_raw,
               payload.dtype.str, payload.shape)
        fn = self._unpackers.get(key)
        if fn is None:
            dt = payload.dtype
            n = payload.nbytes_raw // dt.itemsize
            shape = payload.shape

            def finish(buf):
                seg = buf[:n * dt.itemsize]
                if dt == np.uint8:
                    arr = seg
                else:
                    arr = jax.lax.bitcast_convert_type(
                        seg.reshape(n, dt.itemsize), dt)
                return arr.reshape(shape)

            fn = jax.jit(finish)
            self._unpackers[key] = fn
        return fn(raw)

    def stream(self, items: Iterable[Tuple[np.ndarray, ...]], shardings=None,
               sharded_multi: bool = False):
        """Prefetching transfer stream for sequential consumers (train
        loops): yields each item's device arrays while keeping up to
        `depth` later items' transfers already dispatched — slice t+1
        moves while the scanned epoch for slice t computes.  Each item
        (a tuple of host arrays) rides one packed transfer when the mesh
        is single-device (`put_group`)."""
        buf: deque = deque()
        t0 = time.perf_counter()
        for item in items:
            buf.append(self.put_group(tuple(item), shardings,
                                      sharded_multi=sharded_multi))
            while len(buf) > self.depth:
                yield buf.popleft()
        while buf:
            yield buf.popleft()
        self.telemetry.add(wall_s=time.perf_counter() - t0)

    # ---- the pipelined chunk engine ------------------------------------
    def run(self, chunk_iter, compute_fn: Callable,
            greedy: bool = True) -> List[np.ndarray]:
        """Drive (chunk, n_valid) pairs through transfer + compute with
        decode/transfer/compute overlap; returns per-chunk host outputs
        trimmed to n_valid, in feed order.

        `chunk_iter` is either a plain iterable — it runs on ONE
        prefetch thread (`_IterSource`; decode/assembly overlap device
        compute) — or a `FeedSource` that owns its own production
        concurrency (HostPipeline's N decode workers feed the same
        consumer loop; io/pipeline.py).  Ready chunks coalesce into
        packed groups (same shape/dtype: one [k, bs, ...] buffer; mixed
        on a single device: one byte-packed buffer); each group is ONE
        `device_put`, split apart on device by a donated unpack program,
        and `compute_fn` is dispatched per chunk.  Up to `depth` groups
        are in flight; the oldest drains (async-fetched) when the window
        fills.

        greedy=True never waits for a fuller pack (latency-first; the
        transform path).  greedy=False waits until `coalesce` chunks are
        queued (or the producer is done) before forming each group —
        maximum amortization when total latency is what matters (bulk
        jobs, the microbench)."""
        import jax

        tel = self.telemetry
        t_wall = time.perf_counter()
        if isinstance(chunk_iter, FeedSource):
            source = chunk_iter
        else:
            source = _IterSource(chunk_iter,
                                 maxsize=max(4 * self.coalesce,
                                             2 * self.depth))
        source.start()

        results: List[np.ndarray] = []
        inflight: deque = deque()  # (ys, ns, slot) per group, feed order
        done = False
        leftover: Optional[Tuple[np.ndarray, int]] = None

        def drain_group():
            ys, ns, slot = inflight.popleft()
            t0 = time.perf_counter()
            for y, n in zip(ys, ns):
                results.append(np.asarray(y)[:n])
            tel.add(stall_drain_s=time.perf_counter() - t0)
            if slot is not None:
                slot.busy = False

        while not done or leftover is not None:
            # ---- collect the next group of ready chunks ----
            # a degraded engine forms singleton groups and keeps nothing
            # in flight (the safe unpipelined ladder rung; may flip
            # mid-run when a packed transfer exhausts its retries)
            coalesce_now = 1 if self.degraded else self.coalesce
            group: List[Tuple[np.ndarray, int]] = []
            gbytes = 0
            if leftover is not None:
                group.append(leftover)
                gbytes = leftover[0].nbytes
                leftover = None
            while len(group) < coalesce_now and gbytes < self.coalesce_bytes:
                if not group or (not greedy and not done):
                    t0 = time.perf_counter()
                    item = source.get()
                    tel.add(stall_decode_s=time.perf_counter() - t0)
                else:
                    try:
                        item = source.get_nowait()
                    except queue.Empty:
                        break
                if item is FEED_END:
                    done = True
                    break
                chunk, n = item
                if group and not self._can_pack(group[0][0], chunk):
                    leftover = (chunk, n)
                    break
                group.append((chunk, n))
                gbytes += chunk.nbytes
            if not group:
                continue

            # ---- one transfer for the whole group ----
            xs, slot = self._transfer_group(group)
            t0 = time.perf_counter()
            ys = []
            for x in xs:
                ys.append(compute_fn(x))
            for y in ys:
                try:
                    # start device->host DMA at dispatch so the fetch
                    # overlaps later groups instead of serializing at drain
                    y.copy_to_host_async()
                except (AttributeError, NotImplementedError):
                    pass
            # dispatch time; the blocked remainder of device compute
            # lands in stall_drain_s — the sum is the forward's
            # host-visible cost
            tel.add(compute_s=time.perf_counter() - t0)
            inflight.append((ys, [n for _c, n in group], slot))
            while len(inflight) > (0 if self.degraded else self.depth):
                drain_group()
        while inflight:
            drain_group()
        tel.add(wall_s=time.perf_counter() - t_wall)
        src_err = source.error()
        if src_err is not None:
            raise src_err
        return results

    # ---- packing internals ---------------------------------------------
    def _can_pack(self, a: np.ndarray, b: np.ndarray) -> bool:
        """Chunks pack together when same shape+dtype (array pack) or, on
        a single device, any shapes via the byte-packed wire (a sharded
        byte buffer cannot carry mixed batch axes across shards)."""
        if a.shape == b.shape and a.dtype == b.dtype:
            return True
        return self._dp() == 1 and (self.mesh is None
                                    or self.mesh.devices.size == 1)

    def _acquire_slot(self, key, shape, dtype) -> _RingSlot:
        """Ring slot for a packing buffer: `depth + 1` slots per wire
        shape, reused round-robin.  device_put may alias host memory
        zero-copy (CPU backend), so a busy slot must drain first and a
        fenced slot blocks on its unpacked outputs before rewrite."""
        import jax

        ring = self._rings.setdefault(key, [])
        if not ring:
            ring.extend(_RingSlot() for _ in range(self.depth + 1))
        pos = self._ring_pos.get(key, 0)
        self._ring_pos[key] = (pos + 1) % len(ring)
        slot = ring[pos]
        if slot.fence is not None:
            t0 = time.perf_counter()
            jax.block_until_ready(slot.fence)
            self.telemetry.add(stall_drain_s=time.perf_counter() - t0)
            slot.fence = None
        if slot.buf is None or slot.buf.shape != tuple(shape) \
                or slot.buf.dtype != dtype:
            slot.buf = np.empty(shape, dtype)
        return slot

    def _transfer_group(self, group):
        """ONE device_put for the group; returns (device chunks, ring slot
        or None).  Singletons skip packing entirely (no host copy).  A
        packed transfer that fails all its retries degrades the engine and
        the group falls back to per-chunk singleton transfers."""
        tel = self.telemetry

        def put_one(c):
            sh = self._chunk_sharding(c.ndim)
            t0 = time.perf_counter()
            x = self._device_put(c, sh)
            dt = time.perf_counter() - t0
            tel.add(bytes_moved=c.nbytes, transfer_calls=1,
                    transfer_s=dt, chunks_fed=1, groups=1)
            self._obs_transfer(c.nbytes, dt, 1)
            return x

        chunks = [c for c, _n in group]
        k = len(chunks)
        if k == 1 or self.degraded:
            return [put_one(c) for c in chunks], None

        first = chunks[0]
        homogeneous = all(c.shape == first.shape and c.dtype == first.dtype
                          for c in chunks)
        if homogeneous:
            key = ("pack", k, first.shape, first.dtype.str)
            slot = self._acquire_slot(key, (k,) + first.shape, first.dtype)
            # a slot stays busy until its group drains; _acquire_slot only
            # hands out free slots because the ring has depth+1 entries
            # and the in-flight window is depth
            slot.busy = True
            for i, c in enumerate(chunks):
                slot.buf[i] = c
            t0 = time.perf_counter()
            sh = self._packed_sharding(slot.buf.ndim)
            try:
                packed = self._device_put(slot.buf, sh)
            except Exception as e:  # noqa: BLE001 — degrade, then safe path
                slot.busy = False
                self._degrade(f"packed stack transfer failed after retries: {e}")
                return [put_one(c) for c in chunks], None
            dt = time.perf_counter() - t0
            tel.add(bytes_moved=slot.buf.nbytes, transfer_calls=1,
                    transfer_s=dt, chunks_fed=k, groups=1,
                    coalesced_chunks=k)
            self._obs_transfer(slot.buf.nbytes, dt, k)
            xs = list(self._unpack_stack(packed, k, first.shape,
                                         first.dtype.str))
            return xs, slot

        # mixed shapes/dtypes: byte-pack with an offset header (single
        # device only — _can_pack gates this path)
        layout = []
        off = 0
        for c in chunks:
            layout.append((off, c.shape, c.dtype.str))
            off += -(-c.nbytes // _ALIGN) * _ALIGN
        total = off
        slot = self._acquire_slot(("bytes", total), (total,), np.uint8)
        slot.busy = True
        for c, (o, _s, _d) in zip(chunks, layout):
            slot.buf[o:o + c.nbytes] = c.reshape(-1).view(np.uint8)
        t0 = time.perf_counter()
        try:
            packed = self._device_put(slot.buf)
        except Exception as e:  # noqa: BLE001 — degrade, then safe path
            slot.busy = False
            self._degrade(f"packed byte transfer failed after retries: {e}")
            return [put_one(c) for c in chunks], None
        dt = time.perf_counter() - t0
        tel.add(bytes_moved=total, transfer_calls=1,
                transfer_s=dt, chunks_fed=k, groups=1, coalesced_chunks=k)
        self._obs_transfer(total, dt, k)
        xs = list(self._unpack_bytes(packed, tuple(layout), None))
        return xs, slot

    def _unpack_stack(self, packed, k: int, shape, dtype_str: str):
        """Split a [k, bs, ...] packed buffer into k chunks on device —
        one jitted program per (k, shape) signature, input DONATED so the
        staging HBM is released/aliased at the split."""
        import jax

        key = ("stack", k, tuple(shape), dtype_str)
        fn = self._unpackers.get(key)
        if fn is None:
            out_sh = self._chunk_sharding(len(shape))

            def split(p):
                return tuple(p[i] for i in range(k))

            kw = {"donate_argnums": (0,)}
            if out_sh is not None:
                kw["out_shardings"] = (out_sh,) * k
            fn = jax.jit(split, **kw)
            self._unpackers[key] = fn
            return _first_call(fn, packed)
        return fn(packed)

    def _unpack_bytes(self, packed, layout, shardings):
        """Slice + bitcast + reshape the byte-packed wire buffer back into
        its arrays on device — one jitted program per layout signature
        (offsets are static; serving's per-tick layout is constant, so
        this compiles once)."""
        import jax

        key = ("bytes", layout, tuple(str(s) for s in shardings or ()))
        fn = self._unpackers.get(key)
        if fn is None:
            def unpack(buf):
                outs = []
                for off, shape, dstr in layout:
                    dt = np.dtype(dstr)
                    n = int(np.prod(shape, dtype=np.int64))
                    seg = buf[off:off + n * dt.itemsize]
                    if dt == np.uint8:
                        arr = seg
                    else:
                        arr = jax.lax.bitcast_convert_type(
                            seg.reshape(n, dt.itemsize), dt)
                    outs.append(arr.reshape(shape))
                return tuple(outs)

            kw: Dict[str, Any] = {"donate_argnums": (0,)}
            if shardings is not None and any(s is not None for s in shardings):
                kw["out_shardings"] = tuple(shardings)
            fn = jax.jit(unpack, **kw)
            self._unpackers[key] = fn
            return _first_call(fn, packed)
        return fn(packed)

    # ---- the flow adapter ----------------------------------------------
    def stage(self, workers: int = 1,
              credits: Optional[int] = None) -> "H2DStage":
        """This feed's h2d hop as a graftflow `Stage`, for credit-bounded
        decode -> assemble -> h2d graphs (core/flow.py)."""
        return H2DStage(self, workers=workers, credits=credits)


class H2DStage(Stage):
    """DeviceFeed's h2d hop as a registered flow stage: each item is one
    host array (or a tuple of arrays packed into one transfer) moved
    through the feed's guarded put path — the `feed.device_put`
    StagePolicy retry ladder and the degrade-to-singletons terminal rung
    ride underneath unchanged.  A meshed feed's stage additionally
    shards data-divisible batches straight across the mesh (the
    per-device engine in io/shard_put.py), so the `feed.shard_put`
    ladder and the sticky shard->coalesced degrade rung are exercised by
    credit-bounded graphs too.  The bounded credit budget is the staging
    discipline as a declared number: at most `credits` chunks staged
    host-side per graph (lint rule G405 holds every registered Stage
    subclass to one)."""

    name = "h2d"
    credits = 4

    def __init__(self, feed: Optional[DeviceFeed] = None,
                 workers: int = 1, credits: Optional[int] = None):
        super().__init__(workers=workers, credits=credits)
        self.feed = feed if feed is not None else DeviceFeed()

    def process(self, value):
        if isinstance(value, (tuple, list)):
            return self.feed.put_group(
                tuple(np.asarray(a) for a in value))
        arr = np.asarray(value)
        sharding = None
        if self.feed.mesh is not None and arr.ndim \
                and arr.shape[0] % self.feed._dp() == 0:
            sharding = self.feed._chunk_sharding(arr.ndim)
        return self.feed.put(arr, sharding)
