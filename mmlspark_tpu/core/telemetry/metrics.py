"""Metric primitives behind one process-wide registry.

Three instrument kinds, Prometheus-shaped (the exposition convention):

* **counters** — the PR-4 event ledger (`incr("serving.shed")`),
  monotonic ints.  Kept as a plain dict under one lock: `incr` is called
  from every fault/retry/shed path and must stay a few hundred ns.
* **gauges** — last-written values (queue depths, overlap fractions,
  examples/sec).  `gauge(name).set(v)` / `.inc()`.
* **histograms** — fixed log-spaced buckets, LOCK-STRIPED: each
  observing thread hashes onto one of `_STRIPES` independent
  (lock, counts, sum) shards so the serving hot path never serializes
  on a single histogram lock; snapshots merge the stripes.

Naming convention: ``layer.component.metric`` (e.g.
``serving.request.latency``, ``io.feed.transfer.bytes``).  Every STATIC
name instrumented anywhere in the tree must appear in
``DECLARED_METRICS`` below — `tools/ci.py metrics-lint` greps call sites
and fails on undeclared literals, so a typo'd metric name cannot
silently record into a parallel series nobody scrapes.  Dynamic
per-entity suffixes (``faults.injected.<point>``,
``circuit.open.<host>``) are valid when their PREFIX is declared.
"""
from __future__ import annotations

import bisect
import threading
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["DECLARED_METRICS", "is_declared", "Gauge", "Histogram",
           "MetricsRegistry", "REGISTRY", "default_buckets",
           "BYTE_BUCKETS", "FILL_BUCKETS", "BUCKET_FAMILIES",
           "HISTOGRAM_FAMILY", "buckets_for"]

# ---------------------------------------------------------------------------
# The declared-name table: every static metric/counter name in the tree.
# tools/ci.py `metrics-lint` enforces that instrumented literals resolve
# here (exact match, or prefix match for per-entity families).
# ---------------------------------------------------------------------------
DECLARED_METRICS: Dict[str, str] = {
    # -- counters (telemetry.incr): the resilience event ledger (PR 4)
    "serving.shed": "counter",
    "serving.deadline_expired": "counter",
    "batcher.shed": "counter",
    "batcher.deadline_expired": "counter",
    "feed.transfer_retry": "counter",
    "feed.degraded": "counter",
    # -- counters: the sharded direct-to-chip path (io/shard_put.py, PR 14)
    "feed.shard_retry": "counter",
    "feed.shard_degraded": "counter",
    "io.feed.shard.puts": "counter",
    "io.feed.shard.fallback": "counter",
    "io.feed.shard.compressed_groups": "counter",
    "circuit.open": "counter",            # + .<breaker-name> variants
    "circuit.closed": "counter",
    "circuit.half_open_probe": "counter",
    "faults.injected": "counter",         # + .<fault-point> variants
    "training.autosave": "counter",
    "training.resume": "counter",
    # -- counters: training reliability ladder (models/guard.py, PR 10)
    "training.anomaly": "counter",        # + .<kind> variants
    "training.quarantine": "counter",     # + .skip variant (replay skips)
    "training.rollback": "counter",
    "training.abort": "counter",
    "training.hang": "counter",
    "checkpoint.corrupt": "counter",
    "checkpoint.fallback": "counter",
    "checkpoint.quarantine": "counter",
    "checkpoint.write_failed": "counter",
    "io.pipeline.items": "counter",       # + .<stage> variants
    # -- counters: the graftflow runtime ledger (core/flow.py, PR 12)
    "flow.items": "counter",              # + .<stage> variants
    "flow.shed": "counter",               # + .<stage> variants
    "flow.expired": "counter",            # + .<stage> variants
    # registered Stage subclasses declare their exact rows (G405)
    "flow.shed.admission": "counter",
    "flow.expired.admission": "counter",
    "flow.shed.h2d": "counter",
    "flow.expired.h2d": "counter",
    "flow.shed.prefill": "counter",
    "flow.expired.prefill": "counter",
    "xla.compile.count": "counter",       # every observed XLA compile
    "xla.compile.hot_path": "counter",    # + .<fn> variants: steady-state
    # the persistent cache's answer to each compile, + .<stage> variants
    # (setup / run: core/telemetry/device.py)
    "xla.compile.cache.hits": "counter",       # fetched, not compiled
    "xla.compile.cache.misses": "counter",     # compiled
    "xla.compile.cache.unwritten": "counter",  # compiled, and not kept
    # -- counters: the continuous batcher's loop thread (serving/batcher.py)
    "serving.batcher.prefill.tokens": "counter",         # real prompt tokens
    "serving.batcher.prefill.padded_tokens": "counter",  # rows x bucket computed
    # score tiles the admission flash forward visits for the prompts' own
    # lengths / what their buckets' whole schedules hold
    "serving.batcher.prefill.attn_tiles": "counter",
    "serving.batcher.prefill.attn_tiles_bucket": "counter",
    "serving.batcher.live_tokens": "counter",   # K/V rows read, summed per tick
    # models with two kinds of KV state and routed experts (models/moe_lm.py)
    "serving.batcher.pages.full": "counter",      # pages in use, summed a tick
    "serving.batcher.pages.window": "counter",
    "serving.batcher.pages.latent": "counter",    # a one-pool latent kind
    "serving.batcher.pages.window_recycled": "counter",  # ring entries reused
    "serving.batcher.attended.full": "counter",   # K/V rows a layer attends
    "serving.batcher.attended.window": "counter",
    "serving.batcher.attended.latent": "counter",
    "serving.batcher.prefill.attended.full": "counter",   # admissions' part
    "serving.batcher.prefill.attended.window": "counter",
    "serving.batcher.prefill.attended.latent": "counter",
    "serving.moe.assignments": "counter",         # (token, expert) on experts held
    "serving.moe.experts_touched": "counter",     # (layer, expert) pairs read
    "serving.moe.live_assignments": "counter",    # the same, live rows only
    "serving.moe.load_max": "counter",            # busiest held expert, live rows
    "serving.moe.zero_assignments": "counter",    # live rows' identity experts
    # -- counters: what a trained step of a routed model did
    # (models/training.py record_lm_stats, from models/glm_moe_lm.py's parts)
    "training.moe.assignments": "counter",     # live (token, expert) on experts held
    "training.moe.experts_touched": "counter", # (layer, expert) pairs with a row
    "training.moe.load_max": "counter",        # busiest held expert, a layer a step
    "training.moe.load_max_all": "counter",    # busiest of ALL experts, the same
    "training.attn.pairs": "counter",          # causal (query, key) pairs, all sublayers
    "training.mtp.tokens": "counter",          # targets of the MTP head
    # -- counters: fleet gateway event ledger (serving/fleet.py, PR 9)
    "serving.fleet.retry": "counter",
    "serving.fleet.eject": "counter",
    "serving.fleet.reinstate": "counter",
    "serving.fleet.no_replica": "counter",
    "serving.fleet.deadline_expired": "counter",
    "serving.fleet.rollback": "counter",
    "serving.fleet.promote": "counter",
    # -- counters: federated telemetry plane (core/telemetry/fleet.py, PR 15)
    "fleet.pull": "counter",              # one per completed federated pull
    "fleet.pull_failed": "counter",       # + .<replica> variants
    "fleet.incident": "counter",          # flight-recorder bundles written
    "slo.alert.pending": "counter",       # + .<slo> variants
    "slo.alert.firing": "counter",        # + .<slo> variants
    "slo.alert.resolved": "counter",      # + .<slo> variants
    "autoscale.up": "counter",
    "autoscale.down": "counter",
    # -- counters: elastic multi-host runtime (parallel/distributed.py, PR 19)
    "dist.rendezvous.attempt": "counter",   # one per join attempt
    "dist.rendezvous.retry": "counter",     # backed-off re-attempts
    "dist.rendezvous.failed": "counter",    # deadline/budget exhausted
    "dist.heartbeat.missed": "counter",     # dropped beats (injected/lost)
    "dist.host.lost": "counter",            # + .<host> variants
    "dist.membership.update": "counter",    # published epoch advances
    "dist.membership.stale": "counter",     # rejected stale epochs
    "dist.barrier.timeout": "counter",
    "dist.collective.overrun": "counter",   # hang-budget deadline fired
    # -- counters: goodput plane (core/telemetry/timeseries.py+goodput.py)
    "timeseries.samples": "counter",        # one per TimeSeriesStore sweep
    "training.straggler": "counter",        # + .<host> variants (merge side)
    # -- histograms
    "serving.request.latency": "histogram",
    "serving.batch.fill": "histogram",
    "serving.batcher.batch_fill": "histogram",
    "io.feed.transfer.latency": "histogram",
    "io.feed.transfer.bytes": "histogram",
    "io.feed.shard.latency": "histogram",   # one observation per shard put
    "io.feed.shard.bytes": "histogram",
    "io.pipeline.stage.latency": "histogram",   # labeled {stage=...}
    "flow.stage.latency": "histogram",          # labeled {stage=...}
    "io.http.request.latency": "histogram",
    "models.training.step_latency": "histogram",
    "checkpoint.verify.latency": "histogram",
    # labeled {stage=setup|run}: the compile sentry's stage
    "xla.compile.latency": "histogram",
    "xla.compile.trace.latency": "histogram",   # jaxpr tracing, self time
    "xla.compile.lower.latency": "histogram",   # jaxpr -> MLIR module
    # the continuous batcher's loop thread (serving/batcher.py)
    "serving.batcher.tick.latency": "histogram",    # decode tick less admit
    "serving.batcher.tick.host": "histogram",       # ... less the fetch too
    "serving.batcher.admit.latency": "histogram",   # one admission, all buckets
    "serving.batcher.queue_wait": "histogram",      # submit() -> admission
    "serving.fleet.request.latency": "histogram",   # gateway e2e, labeled
    "serving.fleet.replica.latency": "histogram",   # labeled {replica=...}
    "fleet.scrape.latency": "histogram",    # one full federated pull+merge
    "dist.rendezvous.latency": "histogram",  # join time, per host
    # -- gauges
    "serving.queue.depth": "gauge",
    "serving.batcher.queue_depth": "gauge",
    "io.feed.degraded_engines": "gauge",
    "io.feed.overlap_frac": "gauge",
    "io.feed.stall_s": "gauge",
    "io.feed.queue.depth": "gauge",
    "io.feed.shard.concurrency": "gauge",   # pool in-flight high-water
    "io.feed.shard.wire_ratio": "gauge",    # raw/sent on the RLE wire
    "io.feed.shard.queue.depth": "gauge",   # transfer-pool task backlog
    "io.pipeline.queue.depth": "gauge",   # + .<stage> variants
    "flow.queue.depth": "gauge",          # + .<stage> variants
    "flow.queue.depth.admission": "gauge",
    "flow.queue.depth.h2d": "gauge",
    "flow.queue.depth.prefill": "gauge",
    "core.batching.queue.depth": "gauge",
    "models.training.examples_per_sec": "gauge",
    "training.guard.lr_scale": "gauge",
    "device.hbm.bytes_in_use": "gauge",
    "device.hbm.peak_bytes": "gauge",
    "device.live_buffer_count": "gauge",
    "setup.start_s": "gauge",     # process age when the compile sentry armed
    "serving.fleet.replicas": "gauge",
    "serving.fleet.healthy": "gauge",
    "fleet.pull.replicas": "gauge",       # replicas reached by last pull
    "slo.burn_rate": "gauge",             # + .<slo> variants
    "autoscale.target_replicas": "gauge",
    "dist.membership.epoch": "gauge",     # current membership epoch
    "dist.membership.hosts": "gauge",     # live hosts in the view
    # -- gauges: goodput plane (core/telemetry/goodput.py, PR 20)
    "training.goodput.frac": "gauge",         # productive / wall, whole run
    "training.goodput.window_frac": "gauge",  # same over the last K steps
    "training.goodput.lost_s": "gauge",       # + .<kind> variants
    "training.straggler.ratio": "gauge",      # p_max/p_median at detection
}


def is_declared(name: str) -> bool:
    """Exact member of the table, or a dynamic per-entity child of one
    (``faults.injected.feed.device_put`` under ``faults.injected``)."""
    if name in DECLARED_METRICS:
        return True
    return any(name.startswith(d + ".") for d in DECLARED_METRICS)


# half-decade log spacing, 1 µs .. 1000 s: one default ladder covers
# everything timed in seconds, from a coalesced device_put to a cold
# XLA compile inside a serving tick
def default_buckets() -> Tuple[float, ...]:
    return tuple(10.0 ** (-6 + i / 2.0) for i in range(19))


# power-of-4 spacing, 64 B .. 1 GiB: the transfer-size ladder
BYTE_BUCKETS: Tuple[float, ...] = tuple(float(64 * 4 ** i) for i in range(13))

# linear 0.05 .. 1.0: the fill-fraction ladder (batch occupancy is a
# ratio, not a latency — a log ladder wastes 15 of 19 edges above 1.0)
FILL_BUCKETS: Tuple[float, ...] = tuple(i / 20.0 for i in range(1, 21))

# ---------------------------------------------------------------------------
# Named bucket families.  Every DECLARED histogram must resolve to one of
# these ladders (graftlint M003): fleet-level federation merges replica
# histograms bucket-by-bucket, which is only exact when every replica —
# and every process version in a mixed rollout — shares identical `le`
# edges.  Pinning the ladder at declaration makes edge drift a lint
# error instead of a silently-wrong merged p99.
# ---------------------------------------------------------------------------
BUCKET_FAMILIES: Dict[str, Tuple[float, ...]] = {
    "latency": tuple(10.0 ** (-6 + i / 2.0) for i in range(19)),
    "bytes": BYTE_BUCKETS,
    "fill": FILL_BUCKETS,
}

# declared histogram name -> family key in BUCKET_FAMILIES
HISTOGRAM_FAMILY: Dict[str, str] = {
    "serving.request.latency": "latency",
    "serving.batch.fill": "fill",
    "serving.batcher.batch_fill": "fill",
    "io.feed.transfer.latency": "latency",
    "io.feed.transfer.bytes": "bytes",
    "io.feed.shard.latency": "latency",
    "io.feed.shard.bytes": "bytes",
    "io.pipeline.stage.latency": "latency",
    "flow.stage.latency": "latency",
    "io.http.request.latency": "latency",
    "models.training.step_latency": "latency",
    "checkpoint.verify.latency": "latency",
    "xla.compile.latency": "latency",
    "xla.compile.trace.latency": "latency",
    "xla.compile.lower.latency": "latency",
    "serving.batcher.tick.latency": "latency",
    "serving.batcher.tick.host": "latency",
    "serving.batcher.admit.latency": "latency",
    "serving.batcher.queue_wait": "latency",
    "serving.fleet.request.latency": "latency",
    "serving.fleet.replica.latency": "latency",
    "fleet.scrape.latency": "latency",
    "dist.rendezvous.latency": "latency",
}


def buckets_for(name: str) -> Optional[Tuple[float, ...]]:
    """The family ladder for a declared histogram name (exact or
    per-entity child), or None when the name carries no family."""
    fam = HISTOGRAM_FAMILY.get(name)
    if fam is None:
        for decl, f in HISTOGRAM_FAMILY.items():
            if name.startswith(decl + "."):
                fam = f
                break
    return BUCKET_FAMILIES[fam] if fam is not None else None


_STRIPES = 8


class Gauge:
    """Last-written value; `inc`/`dec` for up-down counts."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0.0  #: guarded-by self._lock

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class _Stripe:
    __slots__ = ("lock", "counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.lock = threading.Lock()
        self.counts = [0] * n_buckets
        self.sum = 0.0
        self.count = 0


class Histogram:
    """Fixed-boundary histogram, lock-striped across observer threads.

    `boundaries` are the bucket UPPER edges (ascending); observations
    above the last edge land in the implicit +Inf bucket.  An
    observation exactly ON an edge counts into that edge's bucket
    (Prometheus `le` semantics — bucket i holds v <= boundaries[i]).
    """

    def __init__(self, name: str,
                 boundaries: Optional[Sequence[float]] = None):
        self.name = name
        bs = tuple(boundaries) if boundaries is not None else default_buckets()
        if list(bs) != sorted(bs) or len(set(bs)) != len(bs):
            raise ValueError(f"histogram boundaries must be strictly "
                             f"ascending, got {bs}")
        self.boundaries: Tuple[float, ...] = bs
        self._stripes = [_Stripe(len(bs) + 1) for _ in range(_STRIPES)]

    def observe(self, value: float) -> None:
        # le semantics: first boundary >= value (bisect_left: an exact
        # edge hit stays in that edge's bucket)
        i = bisect.bisect_left(self.boundaries, value)
        s = self._stripes[threading.get_ident() % _STRIPES]
        with s.lock:
            s.counts[i] += 1
            s.sum += value
            s.count += 1

    # ---- read side -----------------------------------------------------
    def _merged(self) -> Tuple[List[int], float, int]:
        counts = [0] * (len(self.boundaries) + 1)
        total_sum, total_n = 0.0, 0
        for s in self._stripes:
            with s.lock:
                for i, c in enumerate(s.counts):
                    counts[i] += c
                total_sum += s.sum
                total_n += s.count
        return counts, total_sum, total_n

    def percentile(self, q: float) -> Optional[float]:
        """Bucket-interpolated quantile (q in [0, 1]); None when empty.
        Values in the +Inf bucket report the last finite edge — a
        histogram quantile can never resolve beyond its ladder."""
        counts, _s, n = self._merged()
        if n == 0:
            return None
        target = q * n
        cum = 0.0
        for i, c in enumerate(counts):
            cum += c
            if cum >= target and c > 0:
                if i >= len(self.boundaries):
                    return self.boundaries[-1]
                lo = self.boundaries[i - 1] if i > 0 else 0.0
                hi = self.boundaries[i]
                frac = (target - (cum - c)) / c
                return lo + (hi - lo) * frac
        return self.boundaries[-1]

    def snapshot(self) -> Dict[str, object]:
        counts, total_sum, n = self._merged()
        cum, buckets = 0, []
        for i, le in enumerate(self.boundaries):
            cum += counts[i]
            buckets.append((le, cum))
        buckets.append((float("inf"), n))
        return {
            "count": n,
            "sum": total_sum,
            "buckets": buckets,
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "p99": self.percentile(0.99),
        }


def _label_key(labels: Dict[str, str]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class MetricsRegistry:
    """One process-wide home for every instrument.

    Counters keep the exact PR-4 dict semantics (incr / counters /
    reset_counters) so the existing chaos assertions hold; gauges and
    histograms are create-on-first-touch keyed by (name, labels)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}  #: guarded-by self._lock
        self._gauges: Dict[str, Gauge] = {}  #: guarded-by self._lock
        #: guarded-by self._lock
        self._hists: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                          Histogram] = {}
        # the bucket ladder is fixed per NAME: every labeled child of
        # one histogram family must be mergeable/comparable
        self._hist_buckets: Dict[str, Tuple[float, ...]] = {}  #: guarded-by self._lock

    # ---- counters ------------------------------------------------------
    def incr(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counter_values(self, prefix: Optional[str] = None) -> Dict[str, int]:
        with self._lock:
            if prefix is None:
                return dict(self._counters)
            return {k: v for k, v in self._counters.items()
                    if k.startswith(prefix)}

    def reset_counters(self, prefix: Optional[str] = None) -> None:
        with self._lock:
            if prefix is None:
                self._counters.clear()
            else:
                for k in [k for k in self._counters if k.startswith(prefix)]:
                    del self._counters[k]

    # ---- gauges --------------------------------------------------------
    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def gauge_values(self) -> Dict[str, float]:
        with self._lock:
            gauges = list(self._gauges.values())
        return {g.name: g.value for g in gauges}

    # ---- histograms ----------------------------------------------------
    def histogram(self, name: str,
                  boundaries: Optional[Sequence[float]] = None,
                  **labels: str) -> Histogram:
        key = (name, _label_key(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                bs = self._hist_buckets.get(name)
                if bs is None:
                    fam = buckets_for(name)
                    if fam is not None:
                        # declared family names are pinned to their
                        # ladder: an explicit disagreeing `boundaries`
                        # would make fleet merges inexact (M003)
                        if (boundaries is not None
                                and tuple(boundaries) != fam):
                            raise ValueError(
                                f"histogram {name!r} is declared with a "
                                f"bucket family; explicit boundaries "
                                f"must match it")
                        bs = fam
                    else:
                        bs = (tuple(boundaries) if boundaries is not None
                              else default_buckets())
                    self._hist_buckets[name] = bs
                h = self._hists[key] = Histogram(name, bs)
            return h

    def histograms(self) -> Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                                 Histogram]:
        with self._lock:
            return dict(self._hists)

    def reset_all(self) -> None:
        """Tests only: counters, gauges, and histograms back to empty."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._hist_buckets.clear()


REGISTRY = MetricsRegistry()
