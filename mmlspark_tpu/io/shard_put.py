"""Sharded direct-to-chip transfers.

A single monolithic `device_put` serializes the whole batch through one
staging buffer and one transfer stream.  This module splits the transfer
itself (no cell of the benchmark runs it yet: ROADMAP D6):

  * **Per-shard puts.**  A host batch bound for a `NamedSharding` is
    split along its shard boundaries (``sharding.
    addressable_devices_indices_map`` — the generalization of
    SNIPPETS.md [2]'s ``get_naive_sharding``/``shard_params`` pattern)
    and each sub-array rides its OWN ``jax.device_put(slice, device)``
    straight into that chip's addressable shard; the global array is
    assembled zero-copy with ``jax.make_array_from_single_device_arrays``.
    One transfer stream per chip instead of one for the host.
  * **A per-device transfer pool.**  Shard copies dispatch concurrently
    on a process-wide pool of one worker per addressable device
    (daemon threads ``feed-shard-<i>``, bounded task queue) — the link
    is parallel hardware; feeding it serially was the bug.
  * **Pre-pinned, size-bucketed staging.**  Shard slices are copied
    into reusable power-of-two-bucketed staging buffers before dispatch
    (replacing the feed's single monolithic ring slot for this path).
    Buffers are fenced on their device arrays before reuse and live for
    the process, so steady state does no allocation on real chips.  The
    CPU backend's ``device_put`` aliases host memory zero-copy for the
    LIFE of the device array, so there staged buffers are discarded
    instead of recycled (`_host_aliasing`) — a fence orders a transfer,
    it cannot un-alias memory.
  * **The ladder underneath.**  Every per-shard put crosses the
    `feed.shard_put` fault point behind a `core.flow.StagePolicy`
    retry rung; a shard that exhausts its retries raises
    `ShardTransferError` and the owning `DeviceFeed` degrades the
    group (then the engine) to the coalesced single-put path — the
    existing degrade ladder, one rung higher.  Chaos coverage:
    tests/test_shard_put.py + `tools/chaos_soak.py --flow`.

Telemetry rides the declared `io.feed.shard.*` series; per-shard
bandwidth lands in `FeedTelemetry` (`shard_gbps`,
`transfer_concurrency`).  See docs/performance.md ("The sharded and
compressed feed paths").
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core import telemetry as core_telemetry
from ..utils.faults import fault_point
from ..utils.sync import make_lock

__all__ = ["ShardEngine", "ShardTransferError", "StagingBuckets",
           "transfer_pool", "shard_layout"]

_BUCKET_MIN = 1 << 16  # smallest staging bucket: 64 KiB


class ShardTransferError(Exception):
    """A shard transfer failed after its full retry ladder; the caller
    (DeviceFeed) degrades the group to the coalesced path."""


# ---------------------------------------------------------------------------
# The per-device transfer pool: one worker per addressable device, shared
# process-wide (transfers from every DeviceFeed instance ride it).
# ---------------------------------------------------------------------------
class _Task:
    """One submitted transfer: callable + completion latch.  Hand-rolled
    (not concurrent.futures) so the queue stays bounded and the shared
    state is lockset-visible to graftsan."""

    __slots__ = ("fn", "result", "error", "done")

    def __init__(self, fn: Callable[[], Any]):
        self.fn = fn
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()


class _TransferPool:
    """Bounded pool of `workers` daemon transfer threads.  Submissions
    block when the task queue is full (backpressure, never unbounded
    memory); `run_all` dispatches a group and waits for every member,
    re-raising the first error AFTER all have settled so no shard's
    device buffer is abandoned mid-flight."""

    def __init__(self, workers: int):
        self.workers = max(1, int(workers))
        self._q: "queue.Queue[_Task]" = queue.Queue(maxsize=4 * self.workers)
        self._lock = make_lock("io.feed.shard.pool")
        self._inflight = 0  #: guarded-by self._lock
        self._inflight_hw = 0  #: guarded-by self._lock
        for i in range(self.workers):
            threading.Thread(target=self._work, daemon=True,
                             name=f"feed-shard-{i}").start()

    def _work(self):
        while True:
            task = self._q.get()
            try:
                task.result = task.fn()
            except BaseException as e:  # noqa: BLE001 — relayed to submitter
                task.error = e
            finally:
                with self._lock:
                    self._inflight -= 1
                task.done.set()

    def submit(self, fn: Callable[[], Any]) -> _Task:
        task = _Task(fn)
        with self._lock:
            self._inflight += 1
            if self._inflight > self._inflight_hw:
                self._inflight_hw = self._inflight
        self._q.put(task)
        core_telemetry.gauge("io.feed.shard.queue.depth").set(
            self._q.qsize())
        return task

    def concurrency_high_water(self) -> int:
        with self._lock:
            return self._inflight_hw

    def run_all(self, fns: List[Callable[[], Any]]) -> List[Any]:
        tasks = [self.submit(fn) for fn in fns]
        for t in tasks:
            t.done.wait()
        for t in tasks:
            if t.error is not None:
                raise t.error
        return [t.result for t in tasks]


_POOL_LOCK = make_lock("io.feed.shard.pool_registry")
_POOL: Dict[str, _TransferPool] = {}  #: guarded-by _POOL_LOCK


def transfer_pool(workers: Optional[int] = None) -> _TransferPool:
    """The process-wide transfer pool, lazily sized to the addressable
    device count (or `workers` on first call).  One pool for every feed:
    the link's parallelism is a host resource, not a per-consumer one."""
    with _POOL_LOCK:
        pool = _POOL.get("pool")
        if pool is None:
            if workers is None:
                import jax

                workers = max(1, len(jax.local_devices()))
            pool = _TransferPool(workers)
            _POOL["pool"] = pool
        return pool


# ---------------------------------------------------------------------------
# Size-bucketed staging buffers (the "pre-pinned" host side of the path).
# ---------------------------------------------------------------------------
def _bucket_size(nbytes: int) -> int:
    b = _BUCKET_MIN
    while b < nbytes:
        b <<= 1
    return b


class _StagingBuf:
    __slots__ = ("buf", "fence")

    def __init__(self, nbytes: int):
        self.buf = np.empty(nbytes, np.uint8)
        self.fence: Any = None  # device arrays to block on before reuse


class StagingBuckets:
    """Reusable power-of-two-bucketed host staging buffers.

    `acquire(nbytes)` hands out a buffer of the next bucket size up
    (free-listed per bucket; steady state allocates nothing) and
    `release(buf, fence)` returns it carrying the device arrays whose
    transfers must complete before the bytes may be rewritten —
    `device_put` can alias host memory zero-copy on the CPU backend, so
    reuse is fenced exactly like the feed's ring slots.  On a real chip
    the runtime pins these stable host pages for DMA, which is the
    other half of why reuse (not reallocation) matters."""

    def __init__(self, max_per_bucket: int = 16):
        self.max_per_bucket = int(max_per_bucket)
        self._lock = make_lock("io.feed.shard.staging")
        self._free: Dict[int, List[_StagingBuf]] = {}  #: guarded-by self._lock
        self._allocated = 0  #: guarded-by self._lock

    def discard(self, sb: _StagingBuf) -> None:
        """Drop a buffer whose bytes now BACK a live device array (the
        CPU backend's zero-copy `device_put` alias): it must never
        re-enter a free list — a fence orders the transfer but cannot
        un-alias the memory."""
        with self._lock:
            self._allocated -= 1

    def acquire(self, nbytes: int) -> _StagingBuf:
        size = _bucket_size(nbytes)
        with self._lock:
            free = self._free.get(size)
            if free:
                sb = free.pop()
            else:
                sb = _StagingBuf(size)
                self._allocated += 1
        if sb.fence is not None:
            import jax

            jax.block_until_ready(sb.fence)
            sb.fence = None
        return sb

    def release(self, sb: _StagingBuf, fence: Any = None) -> None:
        sb.fence = fence
        with self._lock:
            self._free.setdefault(len(sb.buf), []).append(sb)
            # bound the pool: beyond max_per_bucket the oldest buffer is
            # dropped to the allocator (bursts must not pin memory forever)
            if len(self._free[len(sb.buf)]) > self.max_per_bucket:
                self._free[len(sb.buf)].pop(0)

    def allocated(self) -> int:
        with self._lock:
            return self._allocated


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------
def _host_aliasing() -> bool:
    """True when this backend's `device_put` may alias host memory
    zero-copy for the life of the device array (the CPU backend) rather
    than DMA-copying into device HBM.  Staged buffers must then be
    discarded, never recycled — rewriting one would rewrite the shard
    it backs (tests/test_shard_put.py proves the corruption without
    this gate)."""
    import jax

    return jax.default_backend() == "cpu"


def shard_layout(sharding, shape) -> Optional[List[Tuple[Any, tuple]]]:
    """[(device, index)] per addressable shard, or None when `shape`
    does not divide evenly — `parallel.mesh.addressable_shard_layout`,
    re-exported at the transfer engine's door.  This is SNIPPETS.md
    [2]'s naive-sharding pattern generalized: instead of one replicated
    `device_put` per leaf, every addressable shard gets its own direct
    transfer."""
    from ..parallel.mesh import addressable_shard_layout

    return addressable_shard_layout(sharding, shape)


class ShardEngine:
    """Concurrent per-shard `device_put` under a retry ladder.

    One engine per `DeviceFeed`; the transfer pool and staging buckets
    it uses are process-wide.  `put_sharded` raises
    `ShardTransferError` when any shard exhausts its retries — the
    owning feed degrades that group (and then itself) to the coalesced
    single-put path."""

    def __init__(self, policy=None, telemetry=None,
                 staging: Optional[StagingBuckets] = None,
                 min_shard_bytes: int = 1 << 12):
        from .feed import FEED_TELEMETRY

        self.policy = policy
        self.telemetry = telemetry if telemetry is not None else FEED_TELEMETRY
        self.staging = staging if staging is not None else _STAGING
        # below this per-shard size the fixed per-put cost dominates the
        # parallelism win; the caller should coalesce instead
        self.min_shard_bytes = int(min_shard_bytes)

    # ---- planning ------------------------------------------------------
    def plan(self, arr: np.ndarray, sharding) -> Optional[List[Tuple[Any, tuple]]]:
        """The shard layout when the sharded path applies: a real
        multi-device NamedSharding, an evenly-divisible batch, and
        shards big enough that per-put overhead stays amortized."""
        if sharding is None:
            return None
        layout = shard_layout(sharding, arr.shape)
        if layout is None or len(layout) <= 1:
            return None
        if arr.nbytes // len(layout) < self.min_shard_bytes:
            return None
        return layout

    # ---- the guarded per-shard put -------------------------------------
    def _put_shard(self, view: np.ndarray, device):
        """One shard's transfer: the `feed.shard_put` fault point behind
        the engine's StagePolicy retry rung; exhaustion surfaces as
        ShardTransferError for the feed's degrade rung."""
        import jax

        def attempt(v):
            fault_point("feed.shard_put")
            return jax.device_put(v, device)

        t0 = time.perf_counter()
        try:
            if self.policy is not None:
                out = self.policy.run(attempt, view)
            else:
                out = attempt(view)
        except Exception as e:  # noqa: BLE001 — mapped to the degrade rung
            raise ShardTransferError(
                f"shard transfer to {device} failed after retries: {e}"
            ) from e
        dt = time.perf_counter() - t0
        core_telemetry.incr("io.feed.shard.puts")
        core_telemetry.histogram("io.feed.shard.latency").observe(dt)
        core_telemetry.histogram(
            "io.feed.shard.bytes",
            boundaries=core_telemetry.BYTE_BUCKETS).observe(view.nbytes)
        return out, dt

    # ---- the sharded group put -----------------------------------------
    def put_sharded(self, arr: np.ndarray, sharding,
                    layout: Optional[List[Tuple[Any, tuple]]] = None):
        """`arr` -> one global jax.Array under `sharding`, moved as
        len(layout) concurrent direct-to-device transfers through the
        per-device pool, assembled without another copy."""
        import jax

        if layout is None:
            layout = self.plan(arr, sharding)
        if layout is None:
            raise ShardTransferError(
                f"shape {arr.shape} does not shard evenly under {sharding}")
        pool = transfer_pool()
        staged: List[Tuple[np.ndarray, Optional[_StagingBuf]]] = []
        for _dev, idx in layout:
            piece = arr[idx]
            if piece.flags["C_CONTIGUOUS"] and piece.base is None:
                # already its own contiguous buffer: stage-free
                staged.append((piece, None))
                continue
            sb = self.staging.acquire(piece.nbytes)
            view = sb.buf[:piece.nbytes].view(piece.dtype).reshape(piece.shape)
            np.copyto(view, piece)
            staged.append((view, sb))
        t0 = time.perf_counter()
        try:
            results = pool.run_all([
                (lambda v=view, d=dev: self._put_shard(v, d))
                for (dev, _idx), (view, _sb) in zip(layout, staged)])
        except ShardTransferError:
            for _view, sb in staged:
                if sb is not None:
                    self.staging.release(sb)
            raise
        wall = time.perf_counter() - t0
        shards = [r[0] for r in results]
        put_s = sum(r[1] for r in results)
        alias = _host_aliasing()
        for (_view, sb), shard in zip(staged, shards):
            if sb is None:
                continue
            if alias:
                self.staging.discard(sb)
            else:
                self.staging.release(sb, fence=shard)
        out = jax.make_array_from_single_device_arrays(
            arr.shape, sharding, shards)
        hw = pool.concurrency_high_water()
        self.telemetry.add(bytes_moved=arr.nbytes, transfer_calls=len(shards),
                           transfer_s=wall, shard_puts=len(shards),
                           shard_bytes=arr.nbytes, shard_wall_s=wall,
                           shard_put_s=put_s, sharded_groups=1)
        self.telemetry.note_max(transfer_concurrency=min(len(shards), hw))
        core_telemetry.gauge("io.feed.shard.concurrency").set(hw)
        return out


# process-wide staging buckets: the pinned pages are a host resource
_STAGING = StagingBuckets()
