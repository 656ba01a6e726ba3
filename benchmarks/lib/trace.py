"""From a profiler trace to intervals, and from intervals to numbers.

`load_xplane` reads the `.xplane.pb` that `jax.profiler` writes, with
nothing but JAX (`ProfileData.from_file`), into a `Trace`: per device the
operations that ran on it, and the host's spans (the drivers'
`TraceAnnotation`s among them), all in seconds on the trace's one clock.
A `Trace` also round-trips through JSON, which is how the recorded fixture
under benchmarks/fixtures/ is kept small.

Everything below `Trace` is interval arithmetic on plain tuples, so the
tests can feed it synthetic intervals with known answers.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

# One TPU device plane per chip; its "XLA Ops" line holds one event per
# executed HLO instruction.  Control-flow instructions appear as events
# that CONTAIN their bodies' events: counting them would turn every gap
# inside a scanned loop into busy time.
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
OTHER_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
               "Framework Name Scope", "Source code")
CONTAINER = re.compile(r"^%?(while|conditional|call)([._\-\d]*)$")
COLLECTIVE = (r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
              r"collective-permute)")
DETAIL_STATS = ("hlo_category", "category", "tf_op", "op_name", "long_name",
                "deduplicated_name")


@dataclass
class Trace:
    # device id -> [(name, start_s, dur_s, detail)], sorted by start
    devices: dict = field(default_factory=dict)
    # [(name, start_s, dur_s, thread)]
    host: list = field(default_factory=list)

    def to_json(self) -> dict:
        return {"devices": {str(k): v for k, v in self.devices.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls({int(k): [tuple(e) for e in v]
                    for k, v in obj["devices"].items()},
                   [tuple(e) for e in obj["host"]])

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    def clip(self, t0: float, t1: float) -> "Trace":
        """The part of every interval inside [t0, t1]."""
        def cut(events):
            out = []
            for name, s, d, extra in events:
                a, b = max(s, t0), min(s + d, t1)
                if b > a:
                    out.append((name, a, b - a, extra))
            return out
        return Trace({k: cut(v) for k, v in self.devices.items()},
                     cut(self.host))


def op_name(event_name: str) -> tuple:
    """The TPU runtime names an op event by its whole HLO instruction
    (`%fusion.8 = bf16[...] fusion(...), kind=kOutput, calls=...`): the
    instruction's name, and the start of the rest as detail."""
    name, _, rest = event_name.partition(" = ")
    return name.lstrip("%"), rest[:200]


def find_xplane(log_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str, device_plane=DEVICE_PLANE,
                ops_line: str = OPS_LINE) -> Trace:
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = device_plane.match(plane.name)
        if m:
            lines = [ln for ln in plane.lines if ln.name == ops_line]
            if not lines:
                # a runtime that names the line otherwise: the busiest
                # line that is not one of the known summaries
                rest = [(len(list(ln.events)), ln) for ln in plane.lines
                        if ln.name not in OTHER_LINES]
                lines = [max(rest, key=lambda x: x[0])[1]] if rest else []
            events = trace.devices.setdefault(int(m.group(1)), [])
            for line in lines:
                for e in line.events:
                    stats = dict(e.stats)
                    detail = " ".join(str(stats[k])[:200] for k in DETAIL_STATS
                                      if stats.get(k))
                    name, rest = op_name(e.name)
                    events.append((name, e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9,
                                   f"{rest} {detail}".strip()))
            events.sort(key=lambda x: x[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        trace.host.append((e.name, e.start_ns * 1e-9,
                                           e.duration_ns * 1e-9, line.name))
    trace.host.sort(key=lambda x: x[1])
    return trace


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------
def merge(intervals):
    """Sorted, disjoint (start, end) pairs covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged `a` that merged `b` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def ops(events, pattern=None, exclude=None):
    """Device operations as (name, start, end), without the control-flow
    containers; `pattern`/`exclude` search name and detail."""
    pat = re.compile(pattern) if pattern else None
    exc = re.compile(exclude) if exclude else None
    for name, s, d, detail in events:
        if CONTAINER.match(name):
            continue
        text = f"{name} {detail}"
        if pat and not pat.search(text):
            continue
        if exc and exc.search(text):
            continue
        yield name, s, s + d


def busy(events):
    """Merged intervals in which any operation ran."""
    return merge((s, e) for _n, s, e in ops(events))


def window_of(trace: Trace, marker: str):
    """(start, end) of the host span named `marker` (the drivers wrap the
    traced slice in one); without it, the extent of the device events."""
    spans = [(s, s + d) for name, s, d, _t in trace.host if name == marker]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    edges = [(s, s + d) for ev in trace.devices.values() for _n, s, d, _x in ev]
    if not edges:
        raise ValueError("trace holds neither the window marker nor a "
                         "device operation")
    return min(s for s, _ in edges), max(e for _, e in edges)


def busy_seconds(trace: Trace) -> dict:
    """Per device, the seconds in which an operation ran."""
    return {dev: total(busy(ev)) for dev, ev in trace.devices.items()}


def op_seconds(trace: Trace, pattern, exclude=None) -> dict:
    """Per device, the summed durations of the matching operations."""
    return {dev: sum(e - s for _n, s, e in ops(ev, pattern, exclude))
            for dev, ev in trace.devices.items()}


def exposed_seconds(events, pattern=COLLECTIVE) -> tuple:
    """(seconds the matching ops ran, seconds of those in which nothing
    else ran) on one device."""
    mine = merge((s, e) for _n, s, e in ops(events, pattern))
    others = merge((s, e) for _n, s, e in ops(events, None, pattern))
    return total(mine), total(subtract(mine, others))


def top_ops(events, n: int = 10):
    """[[name, seconds]] of the operations that took most device time,
    by the trace's name without its instance number (an unrolled model
    gives every layer's copy of an op a number of its own); the last
    entry sums the rest."""
    by_name: dict = {}
    for name, s, e in ops(events):
        stem = re.sub(r"[.\d]+$", "", name) or name
        by_name[stem] = by_name.get(stem, 0.0) + (e - s)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    out = [[k, v] for k, v in ranked[:n - 1]]
    rest = sum(v for _k, v in ranked[n - 1:])
    if rest > 0:
        out.append(["(all other ops)", rest])
    return out


def idle_gaps(trace: Trace, device: int, t0: float, t1: float,
              n: int = 10, ignore=(), longest: int = 2000):
    """[[what the host was doing, idle seconds]]: each of the `longest`
    gaps between operations on `device` inside [t0, t1] goes to the host
    span that overlaps it most (the shortest of equals, so the
    innermost), summed by span name; the many short gaps left over are
    one entry.  Spans named in `ignore` (the window's own marker) do not
    count."""
    import numpy as np

    gaps = sorted(subtract([(t0, t1)], busy(trace.devices[device])),
                  key=lambda g: g[0] - g[1])
    host = [(name, s, s + d) for name, s, d, _t in trace.host
            if name not in ignore and s < t1 and s + d > t0]
    names = [h[0] for h in host]
    starts = np.array([h[1] for h in host], np.float64)
    ends = np.array([h[2] for h in host], np.float64)
    by_name: dict = {}
    for gs, ge in gaps[:longest]:
        best = "no host span"
        if names:
            overlap = np.minimum(ends, ge) - np.maximum(starts, gs)
            top = overlap.max()
            if top > 0:
                tied = np.flatnonzero(overlap >= top * (1 - 1e-9))
                best = names[tied[np.argmin((ends - starts)[tied])]]
        by_name[best] = by_name.get(best, 0.0) + (ge - gs)
    rest = total(gaps[longest:])
    if rest > 0:
        by_name["(shorter gaps)"] = rest
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:n]]
