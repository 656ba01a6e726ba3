"""Device-level observability: the XLA compile sentry, HBM memory
gauges, and `jax.profiler` trace annotations.

Everything host-side in this package watches OUR code; this module
watches the runtime underneath it.  Three concerns:

* **Compile sentry** — `track_compiles()` registers a `jax.monitoring`
  event-duration listener (fires synchronously on the compiling thread)
  that records every XLA compile as an `xla.compile` span — a child of
  the active trace when one is open, so a serving request that triggered
  a compile shows it in `/trace/<id>` — plus an `xla.compile.latency`
  histogram observation and an `xla.compile.count` bump.  The two
  phases before the backend compile ride the same listener into
  `xla.compile.trace.latency` (jaxpr tracing, SELF time: a function
  traced inside another's trace is subtracted from the outer one) and
  `xla.compile.lower.latency` (jaxpr to MLIR): with a warm persistent
  cache they, not the backend compile, are what set-up pays.  After the
  caller DECLARES warmup over (`SENTRY.end_warmup()`), every further
  compile is flagged as a steady-state recompile: `xla.compile.hot_path`
  counter + WARNING log.

  Set-up is attributed where it is paid.  The sentry's STAGE is `setup`
  from its installation until the process's first `end_warmup()` and
  `run` after it for good (`reset()` re-arms the flagging, not set-up);
  the three histograms carry it as a `stage` label.  JAX names the
  function in every event (`fun_name`: `f` when traced, `jit(f)` when
  lowered and compiled), and records the persistent cache's answer on
  the compiling thread just before the backend compile it belongs to,
  so each compile is a hit, a miss, or an UNWRITTEN miss (the cache is
  on and did not keep the program: the next run compiles it again) in
  `xla.compile.cache.{hits,misses,unwritten}.<stage>`, and a bounded
  table a stage (`programs(stage)`) holds what each function cost to
  trace, lower and compile or fetch.  When set-up ends the table is
  written once as a `setup_programs` record.  `setup.start_s` is the
  process's age when the sentry was installed: what came before it
  watched (interpreter, imports, backend start).

  What the events do not carry is the argument shapes, so naming the
  shape that forced a steady-state recompile is the job of
  `watch_compiles(fn, name)`: a transparent wrapper around a jitted
  callable that detects a compile during a call (`_cache_size()` delta,
  falling back to the sentry's global compile count) and, in steady
  state, emits a loud `log_verb` record + WARNING naming the argument
  shapes that forced it (`float32[8,224,224,3]`), bumping the per-entry
  `xla.compile.hot_path.<name>` family.

* **Memory gauges** — `sample_device_memory()` folds
  `device.memory_stats()` across local devices into
  `device.hbm.bytes_in_use` / `device.hbm.peak_bytes` and counts
  `client.live_buffers()` into `device.live_buffer_count`.  Backends
  without memory_stats (CPU CI) skip the HBM gauges and keep the buffer
  count — a graceful no-op, never an exception.  The sampler is PASSIVE:
  if jax is not imported, or imported but its backend never initialized,
  sampling returns {} rather than being the thing that grabs a device.
  `start_memory_sampler(interval_s)` runs it on a daemon thread;
  `ServingServer` best-effort samples on every `/metrics` scrape.

* **Device annotations** — when jax is already imported, `span(name)`,
  `phase(name)` and `device_annotation(name)` enter a
  `jax.profiler.TraceAnnotation(name)`; when it is not, they do not.
  There is no switch: with no capture running an annotation costs about
  a microsecond, and under a capture it lands in the profiler's host
  plane, on the one clock the device trace shares.  "Tracing on" is "a
  profile is being captured".  `device_annotation(name)` is for
  already-measured sites (`feed._device_put`) whose spans go through
  `record_span`.

This module imports no jax at module scope — the telemetry package must
stay importable (and `/metrics` servable) in processes that never touch
a device.
"""
from __future__ import annotations

import collections
import contextlib
import os
import re
import sys
import threading
import time
from typing import Any, Dict, Iterable, Optional, Tuple

from . import spans as _spans
from .goodput import LEDGER
from .metrics import REGISTRY
from .records import log_verb, logger

__all__ = ["CompileSentry", "SENTRY", "track_compiles", "watch_compiles",
           "describe_abstract_shapes", "sample_device_memory",
           "MemorySampler", "start_memory_sampler",
           "device_annotation", "process_age_s"]

# the one monitoring event that means "XLA produced an executable";
# jaxpr tracing / MLIR lowering durations ride the same listener as
# phases of the same compile, not separate compiles
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"
_TRACE_EVENT_SUFFIX = "jaxpr_trace_duration"
_LOWER_EVENT_SUFFIX = "jaxpr_to_mlir_module_duration"
# the persistent cache's answer: JAX records `cache_hits` when it fetched
# the executable and `cache_misses` when it WROTE a new entry; a miss it
# does not keep (compiled under `jax_persistent_cache_min_compile_time_secs`,
# under the entry-size floor, ...) records nothing
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"

SETUP, RUN = "setup", "run"
PROGRAMS_KEPT = 256            # names a stage's table keeps ...
OTHER_PROGRAMS = "_other_"     # ... and where the rest are folded
SETUP_REPORT_TOP = 20
_PROGRAM_FIELDS = ("traced", "trace_s", "lower_s", "compile_s",
                   "hits", "misses", "unwritten")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def process_age_s() -> Optional[float]:
    """Seconds since this process was started, from the kernel's record;
    None where there is no /proc."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def _program_name(fun_name: Any) -> str:
    """The function an event is about: JAX names it `f` when it traces it
    and `jit(f)` when it lowers and compiles it; both are `f` here."""
    name = "<unnamed>" if fun_name is None else str(fun_name)
    wrapped = _WRAPPED.match(name)
    return wrapped.group(1) if wrapped else name


def _persistent_cache_on() -> bool:
    """JAX keeps executables across processes: a cache directory is set
    and the cache is enabled."""
    config = getattr(sys.modules.get("jax"), "config", None)
    try:
        return bool(config.jax_compilation_cache_dir
                    and config.jax_enable_compilation_cache)
    except AttributeError:
        return False


def describe_abstract_shapes(args: Iterable[Any],
                             kwargs: Optional[Dict[str, Any]] = None,
                             limit: int = 8) -> str:
    """'float32[8,224,224,3], int32[8]' for the array-like leaves among
    a call's top-level arguments — the shape signature a recompile keys
    on.  Non-array arguments (pytrees of params, static config) are
    skipped: the data batch is what changes shape in practice."""
    parts = []
    values = list(args) + list((kwargs or {}).values())
    for v in values:
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            dims = ",".join(str(int(d)) for d in shape)
        except (TypeError, ValueError):
            dims = str(shape)
        parts.append(f"{dtype}[{dims}]")
        if len(parts) >= limit:
            parts.append("...")
            break
    return ", ".join(parts) if parts else "<no array args>"


class CompileSentry:
    """Process-wide compile watcher.  Starts in WARMUP: compiles are
    recorded (span + histogram + count) but expected.  After
    `end_warmup()` every compile is a steady-state recompile — the exact
    hazard `tpu_model.pad_to_batch` exists to prevent — and is flagged
    loudly.  `reset()` returns to warmup (tests, or a planned
    reconfiguration that legitimately recompiles).  The first
    `end_warmup()` also ends the stage `setup`, for good."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._installed = False
        self._listener_active = False
        self._steady = False
        self._compiles = 0
        self._stage = SETUP
        self._start_s: Optional[float] = None
        #: guarded-by self._lock
        self._programs: Dict[str, Dict[str, Dict[str, float]]] = {
            SETUP: {}, RUN: {}}
        # per thread, the (start, duration) of trace events not yet
        # enclosed by a later one: what an outer trace has to subtract
        self._traces = threading.local()
        # per thread, the cache's answer to the compile in progress
        self._answers = threading.local()

    # ---- state ---------------------------------------------------------
    @property
    def compile_count(self) -> int:
        """Compiles seen by the monitoring listener (0 when unavailable)."""
        with self._lock:
            return self._compiles

    @property
    def listener_active(self) -> bool:
        with self._lock:
            return self._listener_active

    @property
    def in_warmup(self) -> bool:
        with self._lock:
            return not self._steady

    @property
    def stage(self) -> str:
        """`setup` until the first `end_warmup()`, `run` after it."""
        with self._lock:
            return self._stage

    def end_warmup(self) -> None:
        """Declare warmup over: from here, any compile is a hot-path
        recompile and gets flagged.  The first call ends set-up and
        writes its `setup_programs` record."""
        with self._lock:
            self._steady = True
            ended, self._stage = self._stage == SETUP, RUN
        if ended:
            with log_verb(self, "setup_programs", **self.report(SETUP)):
                pass

    def reset(self) -> None:
        """Re-arm warmup; set-up, once ended, stays ended (a planned
        recompile after it, such as a check's, is not set-up)."""
        with self._lock:
            self._steady = False

    # ---- the per-program table -----------------------------------------
    def programs(self, stage: str = SETUP) -> Dict[str, Dict[str, float]]:
        """function name -> calls traced, trace / lower / compile-or-fetch
        seconds, cache hits, misses and unwritten misses, in `stage`: at
        most `PROGRAMS_KEPT` names, the rest under `OTHER_PROGRAMS`."""
        with self._lock:
            return {n: dict(row) for n, row in self._programs[stage].items()}

    def totals(self, stage: Optional[str] = None) -> Dict[str, float]:
        """The table's columns summed, over one stage or both."""
        out = dict.fromkeys(_PROGRAM_FIELDS, 0)
        for s in (stage,) if stage else (SETUP, RUN):
            for row in self.programs(s).values():
                for k in _PROGRAM_FIELDS:
                    out[k] += row[k]
        return out

    def report(self, stage: str = SETUP,
               top: int = SETUP_REPORT_TOP) -> Dict[str, Any]:
        """A stage's start-up report: its totals and the `top` programs
        by trace + lower + compile seconds."""
        rows = self.programs(stage)
        cost = sorted(rows, key=lambda n: -(rows[n]["trace_s"]
                                            + rows[n]["lower_s"]
                                            + rows[n]["compile_s"]))

        def rounded(row):
            return {k: round(v, 4) if k.endswith("_s") else v
                    for k, v in row.items()}

        return {"start_s": self._start_s, "names": len(rows),
                "totals": rounded(self.totals(stage)),
                "programs": [{"name": n, **rounded(rows[n])}
                             for n in cost[:top]]}

    def _row(self, stage: str, name: str) -> Dict[str, float]:
        """The table's row for `name` (the caller holds the lock)."""
        rows = self._programs[stage]
        if name not in rows and len(rows) >= PROGRAMS_KEPT:
            name = OTHER_PROGRAMS
        row = rows.get(name)
        if row is None:
            row = rows[name] = dict.fromkeys(_PROGRAM_FIELDS, 0)
        return row

    @contextlib.contextmanager
    def warmup(self):
        """Compiles inside the block are warmup; steady-state flagging
        (re-)arms when it exits."""
        with self._lock:
            self._steady = False
        try:
            yield self
        finally:
            self.end_warmup()

    # ---- installation --------------------------------------------------
    def install(self) -> "CompileSentry":
        """Idempotently register the jax.monitoring listener.  Without
        jax (or without the monitoring API) the sentry still works in
        wrapper-only mode: `watch_compiles` call sites detect compiles
        via `_cache_size()` deltas."""
        with self._lock:
            if self._installed:
                return self
            self._installed = True
            self._start_s = process_age_s()
        if self._start_s is not None:
            REGISTRY.gauge("setup.start_s").set(self._start_s)
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                self._on_event_duration)
            monitoring.register_event_listener(self._on_event)
        except Exception:
            return self
        with self._lock:
            self._listener_active = True
        return self

    def _on_event(self, event: str, **_kw: Any) -> None:
        # the cache's answer, on the compiling thread, before the
        # backend-compile event it belongs to
        if event == _CACHE_HIT_EVENT:
            self._answers.last = "hit"
        elif event == _CACHE_WRITE_EVENT:
            self._answers.last = "written"

    def _on_event_duration(self, event: str, duration: float,
                           **kw: Any) -> None:
        # fires synchronously on the thread running the compile, so
        # current_context() attributes the span to the request/step that
        # triggered it
        name = _program_name(kw.get("fun_name"))
        duration = float(duration)
        if not event.endswith(_COMPILE_EVENT_SUFFIX):
            try:
                if event.endswith(_TRACE_EVENT_SUFFIX):
                    self._phase("xla.compile.trace.latency", "trace_s", name,
                                self._trace_self_time(duration))
                elif event.endswith(_LOWER_EVENT_SUFFIX):
                    self._phase("xla.compile.lower.latency", "lower_s", name,
                                duration)
            except Exception:
                pass
            return
        answer = getattr(self._answers, "last", None)
        self._answers.last = None
        outcome = "hits" if answer == "hit" else "misses"
        unwritten = answer is None and _persistent_cache_on()
        with self._lock:
            self._compiles += 1
            steady, stage = self._steady, self._stage
            row = self._row(stage, name)
            row["compile_s"] += duration
            row[outcome] += 1
            row["unwritten"] += unwritten
        phase = "steady" if steady else "warmup"
        try:
            REGISTRY.incr("xla.compile.count")
            REGISTRY.histogram("xla.compile.latency",
                               stage=stage).observe(duration)
            REGISTRY.incr(f"xla.compile.cache.{outcome}.{stage}")
            if unwritten:
                REGISTRY.incr(f"xla.compile.cache.unwritten.{stage}")
            _spans.record_span("xla.compile", _spans.current_context(),
                               duration, phase=phase)
            # a compile observed after training started is wall the run
            # can never get back — the goodput ledger drops this until
            # its first recorded step, so warmup stays unattributed
            LEDGER.note_lost("recompile", duration)
            if steady:
                REGISTRY.incr("xla.compile.hot_path")
                logger.warning(
                    "xla.compile.hot_path: steady-state XLA recompile "
                    "(%.3fs backend compile) — a shape/dtype the warmup "
                    "never saw reached a jitted function", duration)
        except Exception:
            # a telemetry listener must never break a compile
            pass

    def _phase(self, hist: str, column: str, name: str,
               seconds: float) -> None:
        """A trace or lower event: the stage's histogram and the
        function's row."""
        with self._lock:
            stage = self._stage
            row = self._row(stage, name)
            row[column] += seconds
            row["traced"] += column == "trace_s"
        REGISTRY.histogram(hist, stage=stage).observe(seconds)

    def _trace_self_time(self, duration: float) -> float:
        """JAX fires a trace event for every jitted function, also for
        one traced inside another's trace, so an outer duration contains
        the inner ones: subtract the events already recorded on this
        thread that the interval [now - duration, now] encloses, or the
        histogram's sum counts nested traces twice.  (JAX stamps the
        event with `time.time()`, so that is the clock here.)"""
        open_ = getattr(self._traces, "open", None)
        if open_ is None:
            # bounded: an entry leaves only when an outer trace encloses
            # it, and a top-level trace has none
            open_ = self._traces.open = collections.deque(maxlen=4096)
        start = time.time() - duration
        nested = 0.0
        while open_ and open_[-1][0] >= start - 1e-4:
            nested += open_.pop()[1]
        open_.append((start, duration))
        return max(0.0, duration - nested)

    # ---- wrapper-side reporting ----------------------------------------
    def note_traced_compile(self, name: str, args: tuple,
                            kwargs: Dict[str, Any]) -> None:
        """A `watch_compiles` wrapper saw its function compile during a
        call.  In warmup this is expected (the listener already counted
        it); in steady state, name the triggering shape loudly."""
        with self._lock:
            steady = self._steady
            listener = self._listener_active
        if not steady:
            return
        shape = describe_abstract_shapes(args, kwargs)
        REGISTRY.incr(f"xla.compile.hot_path.{name}")
        if not listener:
            # no monitoring API: the wrapper is the only counter
            REGISTRY.incr("xla.compile.count")
            REGISTRY.incr("xla.compile.hot_path")
        with log_verb(self, "hot_path_recompile", fn=name, shape=shape):
            pass
        logger.warning(
            "xla.compile.hot_path: %s recompiled in steady state for %s "
            "— pad or bucket inputs so serving/training reuses the "
            "warmed executable", name, shape)


SENTRY = CompileSentry()


def track_compiles() -> CompileSentry:
    """Arm the process-wide compile sentry (idempotent) and return it.
    Call once before warmup; call `.end_warmup()` when the shapes you
    intend to serve/train have all compiled."""
    return SENTRY.install()


class _WatchedFunction:
    """Transparent proxy over a jitted callable that reports compiles to
    the sentry with shape attribution.  Attribute access (`.lower`,
    `.clear_cache`, ...) passes through, so call sites that treat the
    value as a PjitFunction keep working."""

    __slots__ = ("_fn", "_name", "_sentry")

    def __init__(self, fn, name: str, sentry: CompileSentry):
        self._fn = fn
        self._name = name
        self._sentry = sentry

    @property
    def __wrapped__(self):
        return self._fn

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def _marker(self) -> Tuple[str, int]:
        cache_size = getattr(self._fn, "_cache_size", None)
        if cache_size is not None:
            try:
                return ("cache", int(cache_size()))
            except Exception:
                pass
        return ("global", self._sentry.compile_count)

    def __call__(self, *args, **kwargs):
        kind_before, before = self._marker()
        out = self._fn(*args, **kwargs)
        kind_after, after = self._marker()
        if kind_after == kind_before and after > before:
            self._sentry.note_traced_compile(self._name, args, kwargs)
        return out

    def __repr__(self) -> str:
        return f"watch_compiles({self._fn!r}, name={self._name!r})"


def watch_compiles(fn, name: str,
                   sentry: Optional[CompileSentry] = None):
    """Wrap a jitted callable so steady-state recompiles are attributed
    to `name` and the triggering argument shapes.  Arms the sentry's
    monitoring listener as a side effect (the wrapper and the listener
    are two halves of one mechanism: the listener times and counts, the
    wrapper names)."""
    s = sentry if sentry is not None else SENTRY
    s.install()
    return _WatchedFunction(fn, name, s)


# ---- memory gauges --------------------------------------------------------

def _jax_if_initialized():
    """The imported jax module, or None when jax is absent OR its
    backend was never initialized — a metrics scrape must stay passive
    and never be the call that claims a device."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    return jax if xla_bridge.backends_are_initialized() else None


def sample_device_memory(devices=None) -> Dict[str, int]:
    """One best-effort sample of device memory into the gauges.

    Returns the sampled values ({} when jax/backend is unavailable):
    `hbm_bytes_in_use` / `hbm_peak_bytes` summed across local devices
    where the backend reports `memory_stats()` (TPU/GPU; CPU returns
    None and the HBM gauges are simply not written), and
    `live_buffer_count` from each client's `live_buffers()` (works on
    every backend; falls back to `jax.live_arrays()`)."""
    jax = _jax_if_initialized()
    if jax is None:
        return {}
    try:
        devs = list(devices) if devices is not None else jax.local_devices()
    except Exception:
        return {}
    out: Dict[str, int] = {}
    bytes_in_use = peak_bytes = 0
    have_stats = False
    for d in devs:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        have_stats = True
        used = int(stats.get("bytes_in_use", 0))
        bytes_in_use += used
        peak_bytes += int(stats.get("peak_bytes_in_use", used))
    if have_stats:
        REGISTRY.gauge("device.hbm.bytes_in_use").set(bytes_in_use)
        REGISTRY.gauge("device.hbm.peak_bytes").set(peak_bytes)
        out["hbm_bytes_in_use"] = bytes_in_use
        out["hbm_peak_bytes"] = peak_bytes
    n_buffers: Optional[int] = None
    try:
        clients = {id(d.client): d.client for d in devs}
        n_buffers = sum(len(c.live_buffers()) for c in clients.values())
    except Exception:
        try:
            n_buffers = len(jax.live_arrays())
        except Exception:
            n_buffers = None
    if n_buffers is not None:
        REGISTRY.gauge("device.live_buffer_count").set(n_buffers)
        out["live_buffer_count"] = n_buffers
    return out


class MemorySampler:
    """Daemon thread sampling device memory every `interval_s`.  Also a
    context manager: `with MemorySampler(5.0): ...`."""

    def __init__(self, interval_s: float = 5.0, devices=None):
        self.interval_s = float(interval_s)
        self._devices = devices
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "MemorySampler":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._run, name="device-memory-sampler", daemon=True)
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                sample_device_memory(self._devices)
            except Exception:
                pass
            self._stop.wait(self.interval_s)

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None

    def __enter__(self) -> "MemorySampler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def start_memory_sampler(interval_s: float = 5.0,
                         devices=None) -> MemorySampler:
    return MemorySampler(interval_s, devices).start()


# ---- device annotations ---------------------------------------------------

def device_annotation(name: str):
    """A `jax.profiler.TraceAnnotation(name)` when jax is imported, else
    a no-op context — for already-measured sites (`feed._device_put`,
    pipeline workers) whose spans go through `record_span` and so never
    pass through `span()`."""
    return _spans._annotation_for(name)
