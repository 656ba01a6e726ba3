"""Set-up's parts, from the compile sentry's process totals of its stage
`setup` (core/telemetry/device.py): from the sentry's installation, which
run.py makes before the driver's `setup`, to the first `end_warmup()`,
which run.py makes as the window opens.  Nothing after that is set-up:
`verify`'s compiles are the stage `run`.

`part` is one of
  start        gauge `setup.start_s`: the process's age when the sentry
               was installed (interpreter, imports, backend start)
  trace_lower  histograms `xla.compile.trace.latency` (self time) and
               `xla.compile.lower.latency` of the stage
  compile      histogram `xla.compile.latency` of the stage: backend
               compiles and the persistent cache's fetches
  unwritten    counter `xla.compile.cache.unwritten.setup`: compiles the
               cache did not keep, which the next run pays again
  hit_frac     100 x hits / (hits + misses) of the stage's compiles
  unclaimed    the counter table's `setup_s` less start, trace_lower and
               compile: weights, pools, warm-up executions, a serving ramp.
               Not clipped: it reads under zero where two threads compiled
               at once, and the ledger should show that.

A program from before the stage existed records none of these: nothing to
read.  On a chip, `unclaimed` also prints the parts and the sentry's
start-up report (the programs that cost set-up most) as an earlier line.
"""
from __future__ import annotations

import json

STAGE = (("stage", "setup"),)
TRACE_LOWER = ("xla.compile.trace.latency", "xla.compile.lower.latency")
COMPILE = ("xla.compile.latency",)


def stage_seconds(names):
    """Summed seconds of the named histograms' `setup` label sets, or None
    where there is none."""
    from mmlspark_tpu.core import telemetry

    hists = [h for (name, labels), h in telemetry.REGISTRY.histograms().items()
             if name in names and labels == STAGE]
    return sum(float(h.snapshot()["sum"]) for h in hists) if hists else None


def cache_count(kind: str) -> int:
    from mmlspark_tpu.core import telemetry

    return telemetry.REGISTRY.counter_values().get(
        f"xla.compile.cache.{kind}.setup", 0)


def start_s():
    from mmlspark_tpu.core import telemetry

    return telemetry.REGISTRY.gauge_values().get("setup.start_s")


def unclaimed(ctx):
    parts = {"start_s": start_s(), "trace_lower_s": stage_seconds(TRACE_LOWER),
             "compile_s": stage_seconds(COMPILE)}
    if None in parts.values() or "setup_s" not in ctx.counters:
        return None
    rest = ctx.counters["setup_s"] - sum(parts.values())
    _report(dict(parts, unclaimed_s=rest))
    return rest


def _report(parts: dict) -> None:
    import jax

    from mmlspark_tpu.core import telemetry

    if jax.default_backend() == "cpu":
        return          # a CPU run names no timing
    print(json.dumps({
        "line": "setup_parts", **parts,
        **{k: cache_count(k) for k in ("hits", "misses", "unwritten")},
        "setup_programs": telemetry.SENTRY.report("setup")}), flush=True)


def reduce(ctx, part):
    if part == "start":
        return start_s()
    if part == "trace_lower":
        return stage_seconds(TRACE_LOWER)
    if part == "compile":
        return stage_seconds(COMPILE)
    if part == "unwritten":
        return (None if stage_seconds(COMPILE) is None
                else cache_count("unwritten"))
    if part == "hit_frac":
        hits, misses = cache_count("hits"), cache_count("misses")
        return 100.0 * hits / (hits + misses) if hits + misses else None
    if part == "unclaimed":
        return unclaimed(ctx)
    raise ValueError(f"setup_stage: unknown part {part!r}")
