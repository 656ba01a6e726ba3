"""Of the device's idle seconds in the traced slice, the share that lies
inside one of the program's own host spans (names matching `pattern`), in
percent.

Idle is the slice less the union of the device's operations: the same
`busy` as trace_idle_share.  Every instant of it goes to the INNERMOST
matching span that covers it (the shortest of those open at that instant),
so a gap that runs from one tick's emit through the next tick's intake,
upload and dispatch is split among them.  An earlier line gives, for every
span name, idle and span milliseconds per `per` (ticks), and what no
matching span covered.  No trace, or no matching span in it (a program
from before the spans existed), is nothing to read.
"""
import json
import re

import numpy as np

from lib import trace as tr

NO_SPAN = "(no matching span)"


def innermost(spans):
    """[(name, start, end)] -> disjoint [(name, a, b)] in time order: each
    stretch between two span edges, under the shortest span open in it."""
    edges = sorted({t for _n, s, e in spans for t in (s, e)})
    opening, closing = {}, {}
    for i, (_n, s, e) in enumerate(spans):
        opening.setdefault(s, []).append(i)
        closing.setdefault(e, []).append(i)
    out, open_, prev = [], set(), None
    for t in edges:
        if open_ and t > prev:
            best = min(open_, key=lambda i: spans[i][2] - spans[i][1])
            out.append((spans[best][0], prev, t))
        open_.difference_update(closing.get(t, ()))
        open_.update(i for i in opening.get(t, ())
                     if spans[i][2] > spans[i][1])
        prev = t
    return out


def idle_inside(gaps, a, b):
    """Seconds of the merged, sorted `gaps` inside each [a[i], b[i]]."""
    gs = np.array([g[0] for g in gaps], np.float64)
    ge = np.array([g[1] for g in gaps], np.float64)
    before = np.concatenate([[0.0], np.cumsum(ge - gs)])

    def upto(t):
        i = np.searchsorted(gs, t, side="right")
        j = np.maximum(i - 1, 0)
        return np.where(i > 0, before[j] + np.minimum(t, ge[j]) - gs[j], 0.0)

    return upto(np.asarray(b, np.float64)) - upto(np.asarray(a, np.float64))


def reduce(ctx, pattern, per="ticks"):
    if ctx.trace is None or not ctx.slice.get("seconds"):
        return None
    pat = re.compile(pattern)
    t0, t1 = ctx.slice["t0"], ctx.slice["t1"]
    spans = [(n, s, s + d) for n, s, d, _t in ctx.trace.host if pat.search(n)]
    if not spans:
        return None
    pieces = innermost(spans)
    idle_by, span_by = {}, {}
    for n, s, e in spans:
        span_by[n] = span_by.get(n, 0.0) + (e - s)
    idle = 0.0
    for events in ctx.trace.devices.values():
        gaps = tr.subtract([(t0, t1)], tr.busy(events))
        idle += tr.total(gaps)
        if not gaps:
            continue
        inside = idle_inside(gaps, [p[1] for p in pieces],
                             [p[2] for p in pieces])
        for (n, _a, _b), seconds in zip(pieces, inside):
            idle_by[n] = idle_by.get(n, 0.0) + float(seconds)
    devices = max(len(ctx.trace.devices), 1)
    explained = sum(idle_by.values())
    units = ctx.slice.get(per) or 0

    def ms_per_unit(seconds):
        return 1e3 * seconds / units if units else None

    phases = {n: {"idle_ms": ms_per_unit(idle_by.get(n, 0.0) / devices),
                  "span_ms": ms_per_unit(span_by[n])}
              for n in sorted(span_by)}
    print(json.dumps({"line": "idle_by_phase", "per": per, per: units,
                      "idle_s": idle / devices,
                      "explained_s": explained / devices,
                      NO_SPAN: (idle - explained) / devices,
                      "phases": phases}), flush=True)
    return 100.0 * explained / idle if idle > 0 else None
