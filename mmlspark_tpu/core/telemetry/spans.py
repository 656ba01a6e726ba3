"""Dapper-style spans: trace-id / span-id / parent-id wall-time records.

A **trace** is one logical request's causal tree; a **span** is one
timed operation inside it.  The current (trace_id, span_id) pair lives
in a `contextvars.ContextVar` — per-thread by construction (each thread
starts from an empty context), and correctly scoped under async/greenlet
frameworks that propagate contexts.  Crossing an EXPLICIT thread
boundary (a serving batch loop picking up a held request, a stream pool
worker) re-activates the recorded pair via `use_trace(ctx)`; crossing a
PROCESS boundary rides the `X-Trace-Id` / `X-Span-Id` HTTP headers
(`trace_headers()` injects on the client, `extract_trace()` continues on
the server).

Finished spans land in a bounded ring (`recent_spans`) and a bounded
per-trace index (`get_trace`/`span_tree` — what `/trace/<id>` serves).
Both are capped, so always-on span recording cannot grow host memory;
the caps drop OLDEST whole traces first (a live investigation wants the
most recent requests).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["span", "phase", "record_span", "use_trace", "current_context",
           "current_trace_id", "trace_headers", "extract_trace",
           "get_trace", "span_tree", "recent_spans", "clear_spans",
           "MAX_SPANS", "MAX_TRACES", "MAX_SPANS_PER_TRACE"]

MAX_SPANS = 8192          # global recent-span ring
MAX_TRACES = 512          # distinct trace ids indexed for /trace/<id>
MAX_SPANS_PER_TRACE = 2048

# (trace_id, span_id) of the CURRENT span, or None outside any trace
_CTX: "contextvars.ContextVar[Optional[Tuple[str, str]]]" = \
    contextvars.ContextVar("mmlspark_tpu_trace", default=None)

_LOCK = threading.Lock()
_SPANS: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=MAX_SPANS)
_TRACES: "collections.OrderedDict[str, List[Dict[str, Any]]]" = \
    collections.OrderedDict()


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


_NO_ANNOTATION = contextlib.nullcontext()     # stateless: shared


def _annotation_for(name: str):
    """A `jax.profiler.TraceAnnotation(name)` when jax is already
    imported, else a no-op context: the one way onto the profiler's
    clock.  With no capture running the annotation costs about a
    microsecond; with one running it lands in the profiler's own host
    plane, the only clock the device trace shares.  `sys.modules` and
    not an import: this package stays importable, and `/metrics`
    servable, without jax."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NO_ANNOTATION
    try:
        return profiler.TraceAnnotation(name)
    except Exception:
        return _NO_ANNOTATION


class phase:
    """`span()`'s light sibling for loops: enters the profiler
    annotation `name` and, given `hist` (a `Histogram`), adds the
    elapsed `perf_counter` seconds to it on exit.  No ids, no ring, no
    lock beyond the histogram's stripe, so a loop that ticks every few
    milliseconds can wrap each of its phases in one.  `elapsed_s` holds
    the last exit's reading."""

    __slots__ = ("_annotation", "_hist", "_t0", "elapsed_s")

    def __init__(self, name: str, hist=None):
        self._annotation = _annotation_for(name)
        self._hist = hist
        self.elapsed_s = 0.0

    def __enter__(self) -> "phase":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed_s = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._hist is not None:
            self._hist.observe(self.elapsed_s)


def current_context() -> Optional[Tuple[str, str]]:
    """The active (trace_id, span_id), or None."""
    return _CTX.get()


def current_trace_id() -> Optional[str]:
    ctx = _CTX.get()
    return ctx[0] if ctx is not None else None


def _store(rec: Dict[str, Any]) -> None:
    with _LOCK:
        _SPANS.append(rec)
        tid = rec["trace_id"]
        spans = _TRACES.get(tid)
        if spans is None:
            while len(_TRACES) >= MAX_TRACES:
                _TRACES.popitem(last=False)
            spans = _TRACES[tid] = []
        if len(spans) < MAX_SPANS_PER_TRACE:
            spans.append(rec)


class _Span:
    """Handle yielded by span(): ids plus a mutable attr dict the body
    can annotate (outcome, sizes) before the record is stored."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], attrs: Dict[str, Any]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs


@contextlib.contextmanager
def span(name: str, parent_ctx: Optional[Tuple[str, str]] = None,
         **attrs: Any):
    """Open a span: child of the current context (or of `parent_ctx`,
    e.g. one extracted from request headers); a fresh trace root when
    neither exists.  Wall time and a raised exception's type are
    captured; the exception propagates."""
    parent = parent_ctx if parent_ctx is not None else _CTX.get()
    trace_id = parent[0] if parent else _new_id()
    span_id = _new_id()
    sp = _Span(name, trace_id, span_id,
               parent[1] if parent else None, dict(attrs))
    token = _CTX.set((trace_id, span_id))
    annotation = _annotation_for(name)
    t_start = time.time()
    t0 = time.perf_counter()
    err: Optional[str] = None
    try:
        with annotation:
            yield sp
    except BaseException as e:  # noqa: BLE001 — recorded, then re-raised
        err = type(e).__name__
        raise
    finally:
        _CTX.reset(token)
        rec: Dict[str, Any] = {
            "name": name,
            "trace_id": trace_id,
            "span_id": span_id,
            "parent_id": sp.parent_id,
            "t_start": t_start,
            "wall_s": round(time.perf_counter() - t0, 6),
            "tid": threading.get_ident(),
        }
        if err:
            rec["error"] = err
        if sp.attrs:
            rec["attrs"] = sp.attrs
        _store(rec)


def record_span(name: str, ctx: Optional[Tuple[str, str]], wall_s: float,
                **attrs: Any) -> Dict[str, Any]:
    """Record an already-measured span as a child of `ctx` — the
    cross-thread shape (a batch loop attributing queue wait to the
    handler thread's request span) where a context manager can't wrap
    the producer.  With ctx=None the span roots a fresh trace (the
    compile sentry recording an XLA compile that fired outside any
    request)."""
    rec: Dict[str, Any] = {
        "name": name,
        "trace_id": ctx[0] if ctx is not None else _new_id(),
        "span_id": _new_id(),
        "parent_id": ctx[1] if ctx is not None else None,
        "t_start": time.time() - wall_s,
        "wall_s": round(float(wall_s), 6),
        "tid": threading.get_ident(),
    }
    if attrs:
        rec["attrs"] = dict(attrs)
    _store(rec)
    return rec


@contextlib.contextmanager
def use_trace(ctx: Optional[Tuple[str, str]]):
    """Re-activate a recorded (trace_id, span_id) on THIS thread (the
    explicit thread-hop propagation).  None is a no-op, so call sites
    can pass a request's maybe-absent context unconditionally."""
    if ctx is None:
        yield
        return
    token = _CTX.set((ctx[0], ctx[1]))
    try:
        yield
    finally:
        _CTX.reset(token)


# ---- HTTP propagation ----------------------------------------------------

TRACE_HEADER = "X-Trace-Id"
SPAN_HEADER = "X-Span-Id"


def trace_headers(headers: Optional[Dict[str, str]] = None
                  ) -> Dict[str, str]:
    """Copy of `headers` with the current trace context injected (the
    client half of propagation).  Outside any trace, or when the caller
    already set the headers, the copy is returned unchanged."""
    out = dict(headers or {})
    ctx = _CTX.get()
    if ctx is not None:
        out.setdefault(TRACE_HEADER, ctx[0])
        out.setdefault(SPAN_HEADER, ctx[1])
    return out


def extract_trace(headers) -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) from request headers, case-insensitively
    (the server half).  A trace id without a span id is continued with
    an empty parent — the upstream did not tell us which span sent it."""
    tid = sid = None
    for k in headers.keys():
        lk = k.lower()
        if lk == "x-trace-id":
            tid = str(headers[k])
        elif lk == "x-span-id":
            sid = str(headers[k])
    if not tid:
        return None
    return (tid, sid or "")


# ---- read side -----------------------------------------------------------

def get_trace(trace_id: str) -> List[Dict[str, Any]]:
    """Every recorded span of one trace, in completion order."""
    with _LOCK:
        return list(_TRACES.get(trace_id, ()))


def span_tree(trace_id: str) -> List[Dict[str, Any]]:
    """The trace's spans nested parent->children (roots returned; a span
    whose parent was sent by a remote upstream roots locally)."""
    spans = get_trace(trace_id)
    nodes = {s["span_id"]: dict(s, children=[]) for s in spans}
    roots: List[Dict[str, Any]] = []
    # completion order ≠ start order: children finish before parents, so
    # sort siblings by start time for a readable tree
    for s in sorted(nodes.values(), key=lambda r: r["t_start"]):
        parent = nodes.get(s["parent_id"]) if s["parent_id"] else None
        if parent is not None:
            parent["children"].append(s)
        else:
            roots.append(s)
    return roots


def recent_spans(n: Optional[int] = None) -> List[Dict[str, Any]]:
    with _LOCK:
        out = list(_SPANS)
    return out if n is None else out[-n:]


def clear_spans() -> None:
    with _LOCK:
        _SPANS.clear()
        _TRACES.clear()
