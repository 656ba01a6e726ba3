"""One run of one cell of BENCHMARK.json.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process loads, warms up every shape the cell's traffic uses (set-up),
measures for `--seconds`, compares with the plain reference, and prints as
its LAST stdout line one JSON object: `correct`, `attempted`, `failed`,
`metrics`, `device`, and in a traced run `breakdown`.  Earlier lines carry
what else is worth keeping.  Without a TPU, or with another number of chips
than the cell names, it exits non-zero and prints no result.

Nothing here lists cells, configurations or metrics.  A cell is an entry in
BENCHMARK.json plus `workloads/<cell>.json` (driver, parameters, the metrics
it reports), which names `configs/<config>.json`, `traffic/<traffic>.json`,
`drivers/<kind>.py` and `metrics/<metric>.json`; a metric file names one of
`reducers/<name>.py` and its arguments.  A later cell is new files and one
new entry, never an edit.

`--check` validates BENCHMARK.json against those files.  `--rehearse`
drives a cell's whole path on the CPU at the tiny sizes its files carry and
prints counts and `correct`, never a timing.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")
MARKER = "bench.trace_slice"       # host span the traced slice is wrapped in

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def log(record: dict) -> None:
    """An earlier line: kept by whoever keeps the output, read by no one
    who judges."""
    print(json.dumps(record), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's record:
    interpreter start-up and imports belong to set-up."""
    with open("/proc/self/stat") as f:
        ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - ticks / os.sysconf("SC_CLK_TCK")


# --------------------------------------------------------------------------
# the cell, from its files
# --------------------------------------------------------------------------
class Cell:
    """Everything one run needs to know, read from the files by name."""

    def __init__(self, manifest: dict, name: str, rehearse: bool):
        entry = next((w for w in manifest["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
        config_entry = next(c for c in manifest["configs"]
                            if c["name"] == entry["config"])
        self.name = name
        self.chips = int(entry["chips"])
        self.rehearse = rehearse
        self.config = load_json(ROOT, config_entry["file"])
        self.traffic = load_json(BENCH, "traffic", entry["traffic"] + ".json")
        self.workload = load_json(BENCH, "workloads", name + ".json")
        self.params = dict(self.workload.get("params", {}))
        if rehearse:
            # the tiny sizes ride in the same files, under "rehearse"
            self.config = {**self.config, **self.config.get("rehearse", {})}
            self.traffic = {**self.traffic, **self.traffic.get("rehearse", {})}
            self.params.update(self.workload.get("rehearse", {}))
        self.driver = importlib.import_module(
            "drivers." + self.workload["driver"])

    def metric_files(self, trace: bool) -> dict:
        """name -> (metric file, this cell's argument overrides)."""
        wanted = self.workload["per_layer" if trace else "end_to_end"]
        return {n: (load_json(BENCH, "metrics", n + ".json"), over or {})
                for n, over in wanted.items()}


class Slice:
    """Profiles a short steady slice of the window: the driver calls
    `poll()` between its units of work; the slice opens `start_after_s`
    into the window and closes `seconds` later, and `units()` (the
    driver's running counts) is read at both edges so that trace times
    can be put per step, per tick or per thousand images."""

    def __init__(self, enabled: bool, log_dir: str, start_after_s: float,
                 seconds: float):
        self.log_dir = log_dir
        self.start_after_s = start_after_s
        self.seconds = seconds
        self.units = lambda: {}
        self.t_window = None
        self.t_on = None
        self.done = not enabled
        self.units_on = {}
        self.delta = {}
        self.overhead_s = 0.0
        self._span = None
        self.on_open = lambda: None

    def open_window(self, units) -> None:
        """The driver's word that set-up is over and the window starts."""
        self.on_open()
        self.units = units
        self.t_window = time.monotonic()

    def poll(self) -> None:
        if self.done:
            return
        now = time.monotonic()
        if self.t_on is None:
            if now - self.t_window < self.start_after_s:
                return
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(MARKER)
            self._span.__enter__()
            self.units_on = dict(self.units())
            self.t_on = time.monotonic()
            self.overhead_s += self.t_on - now
        elif now - self.t_on >= self.seconds:
            self.close()

    def close(self) -> None:
        if self.done or self.t_on is None:
            self.done = True
            return
        import jax

        t0 = time.monotonic()
        off = dict(self.units())
        self._span.__exit__(None, None, None)
        self.delta = {k: off[k] - self.units_on.get(k, 0) for k in off}
        self.delta["seconds"] = t0 - self.t_on
        jax.profiler.stop_trace()
        self.done = True
        self.overhead_s += time.monotonic() - t0


class Context:
    """What a reducer may read."""

    def __init__(self, cell: Cell, peaks: dict):
        self.config, self.traffic, self.params = (cell.config, cell.traffic,
                                                  cell.params)
        self.chips = cell.chips
        self.peaks = peaks
        self.counters: dict = {}     # deltas over the window, flat names
        self.samples: dict = {}      # name -> list of readings
        self.trace = None            # lib.trace.Trace clipped to the slice
        self.slice: dict = {}        # units and seconds inside the slice


def program_counters() -> dict:
    """The program's own counters, flat: telemetry counters, histogram
    counts and sums, pipeline stages, feed."""
    from mmlspark_tpu.core import telemetry
    from mmlspark_tpu.io.feed import FEED_TELEMETRY
    from mmlspark_tpu.io.pipeline import PIPELINE_TELEMETRY

    flat = {k: float(v) for k, v in telemetry.counters().items()}
    for (name, _labels), h in telemetry.REGISTRY.histograms().items():
        snap = h.snapshot()
        flat[f"hist.{name}.count"] = (flat.get(f"hist.{name}.count", 0.0)
                                      + float(snap["count"]))
        flat[f"hist.{name}.sum"] = (flat.get(f"hist.{name}.sum", 0.0)
                                    + float(snap["sum"]))
    for stage, rec in PIPELINE_TELEMETRY.snapshot().items():
        for k, v in rec.items():
            flat[f"pipeline.{stage}.{k}"] = float(v)
    for k, v in FEED_TELEMETRY.snapshot().items():
        if isinstance(v, (int, float)):
            flat[f"feed.{k}"] = float(v)
    return flat


def reduce_metrics(ctx: Context, files: dict) -> dict:
    """Each metric through its reducer; one that finds nothing to read
    returns None and is left out."""
    out = {}
    for name, (spec, override) in files.items():
        reducer = importlib.import_module("reducers." + spec["reducer"])
        value = reducer.reduce(ctx, **{**spec.get("args", {}), **override})
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def device_record(devices, ctx: Context, traced: bool) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in devices]
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(max(peaks))}
    if traced and ctx.trace is not None:
        from lib import trace as tr

        busy = tr.busy_seconds(ctx.trace)
        rec["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        rec["window_s"] = ctx.slice["seconds"]
    return rec


def breakdown(ctx: Context) -> dict:
    from lib import trace as tr

    first = min(ctx.trace.devices)
    t0, t1 = ctx.slice["t0"], ctx.slice["t1"]
    return {"device_ops": tr.top_ops(ctx.trace.devices[first]),
            "idle_gaps": tr.idle_gaps(ctx.trace, first, t0, t1,
                                      ignore=(MARKER,))}


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def run(args) -> int:
    manifest = load_json(ROOT, "BENCHMARK.json")
    rehearse = args.rehearse
    cell = Cell(manifest, args.workload, rehearse)
    if rehearse:
        # the CPU, with as many virtual devices as the cell has chips; set
        # before JAX is imported, by anyone
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")

    if not rehearse:
        from mmlspark_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if rehearse:
        devices = devices[:cell.chips]
    else:
        if devices[0].platform != "tpu":
            print(f"run.py: needs a TPU, JAX found {devices[0].platform!r}; "
                  "there is no CPU mode (see --rehearse)", file=sys.stderr)
            return 1
        if len(devices) != cell.chips:
            print(f"run.py: cell {cell.name!r} needs {cell.chips} chip(s), "
                  f"JAX sees {len(devices)}", file=sys.stderr)
            return 1
    from lib.compile_meter import CompileMeter
    from lib.peaks import peaks_for
    from mmlspark_tpu.core import telemetry

    peaks = ({"flops": 1.0, "hbm_bytes": 1.0} if rehearse
             else peaks_for(devices[0].device_kind))
    meter = CompileMeter()
    sentry = telemetry.track_compiles()
    sentry.reset()
    os.makedirs(OUT, exist_ok=True)
    trace_dir = os.path.join(OUT, "trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    seconds = float(cell.params["rehearse_seconds"] if rehearse
                    else args.seconds)
    log({"line": "start", "workload": cell.name, "seed": args.seed,
         "seconds": seconds, "trace": args.trace, "rehearse": rehearse,
         "jax": jax.__version__,
         "compile_cache_dir": None if rehearse else cache_dir})

    ctx = Context(cell, peaks)
    tracing = bool(args.trace)
    slice_ = Slice(tracing, trace_dir,
                   float(cell.params.get("trace_after_s", 2.0)),
                   float(cell.params.get("trace_seconds", 3.0)))
    env = Env(cell, args.seed, devices, seconds, slice_, meter)
    state = cell.driver.setup(env)
    try:
        opened = {}

        def on_open():
            # from here a compile is a hot-path compile
            opened["setup"] = meter.snapshot()
            opened["setup_s"] = process_age_s()
            opened["before"] = program_counters()
            sentry.end_warmup()

        slice_.on_open = on_open
        measured = cell.driver.measure(env, state)
        slice_.close()
        after = program_counters()
        sentry.reset()
        setup, setup_s, before = (opened["setup"], opened["setup_s"],
                                  opened["before"])
        ctx.counters = {k: after[k] - before.get(k, 0.0) for k in after}
        # a sentry that never fired has no counter: that is a count of 0
        ctx.counters.setdefault("xla.compile.hot_path", 0.0)
        ctx.counters.update(measured.get("counters", {}))
        ctx.counters.update({"setup_s": setup_s,
                             "compile_s": setup["compile_s"],
                             "trace_overhead_s": slice_.overhead_s})
        ctx.samples = measured.get("samples", {})
        window = {"line": "window",
                  "window_compiles": ctx.counters.get("xla.compile.hot_path",
                                                      0.0),
                  "setup": {k: setup[k] for k in
                            ("compiles", "cache_hits", "cache_misses")}}
        if not rehearse:     # a CPU run names no timing, here either
            window.update({
                "setup_s": setup_s, "compile_s": setup["compile_s"],
                "notes": measured.get("notes", {}),
                "memory": {str(d.id): d.memory_stats() for d in devices}})
        log(window)
        verdict = cell.driver.verify(env, state, measured)
        log({"line": "verify", **verdict})
    finally:
        cell.driver.close(state)

    if tracing and slice_.delta:
        from lib import trace as tr

        full = tr.load_xplane(tr.find_xplane(trace_dir),
                              **cell.params.get("trace_planes", {}))
        if rehearse and not full.devices:
            full = None        # the CPU backend has no device plane
        else:
            t0, t1 = tr.window_of(full, MARKER)
            ctx.trace = full.clip(t0, t1)
            ctx.slice = {**slice_.delta, "t0": t0, "t1": t1}
            # what the reducers read, kept beside the raw trace so that a
            # pattern can be tried again without the chip
            ctx.trace.save(os.path.join(trace_dir, "slice.trace.json.gz"))
            with open(os.path.join(trace_dir, "slice.json"), "w") as f:
                json.dump(ctx.slice, f)
            if not any(ctx.trace.devices.values()):
                print("run.py: no operation ran on the device in the traced "
                      "slice", file=sys.stderr)
                return 1
    metrics = reduce_metrics(ctx, cell.metric_files(tracing))
    result = {"correct": bool(verdict["correct"]),
              "attempted": int(measured["attempted"]),
              "failed": int(measured["failed"])}
    if rehearse:
        # counts and correctness only: a CPU run never names a timing
        result.update({"rehearse": True, "reduced": sorted(metrics),
                       "device": {"platform": devices[0].platform,
                                  "count": len(devices)}})
    else:
        result.update({"metrics": metrics,
                       "device": device_record(devices, ctx, tracing)})
        if tracing and ctx.trace is not None:
            result["breakdown"] = breakdown(ctx)
    print(json.dumps(result), flush=True)
    return 0 if (result["correct"] or rehearse) else 1


class Env:
    """What a driver is handed: the cell's files, the seed, the devices,
    the window's length and the traced slice to poll."""

    def __init__(self, cell: Cell, seed: int, devices, seconds: float,
                 slice_: Slice, meter):
        self.config, self.traffic, self.params = (cell.config, cell.traffic,
                                                  cell.params)
        self.rehearse = cell.rehearse
        self.seed = seed
        self.devices = devices
        self.seconds = seconds
        self.slice = slice_
        self.meter = meter
        self.out_dir = OUT
        self.log = log


# --------------------------------------------------------------------------
# --check
# --------------------------------------------------------------------------
def check(manifest_path: str) -> list:
    """Every way in which BENCHMARK.json and the files disagree with the
    contract or with each other; empty when they agree."""
    bad = []
    root = os.path.dirname(os.path.abspath(manifest_path))
    m = load_json(manifest_path)
    bench = os.path.join(root, m["paths"][0])

    def name_ok(what, n):
        if not isinstance(n, str) or not NAME.match(n):
            bad.append(f"{what}: name {n!r} outside the allowed characters "
                       "or length")

    def line_ok(what, s):
        if (not isinstance(s, str) or not 1 <= len(s) <= 200
                or "\n" in s or "\t" in s):
            bad.append(f"{what}: needs 1 to 200 characters on one line")

    if sorted(m) != sorted(["command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"]):
        bad.append(f"top-level keys are {sorted(m)}")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        bad.append("run_seconds must be a whole number from 1 to 51")
    for word in m["command"]:
        line_ok("command", word)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    layer = {x["name"]: x for x in m["per_layer"]}
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    for group, n in ((m["end_to_end"], len(e2e)), (m["per_layer"], len(layer)),
                     (m["workloads"], len(cells)), (m["configs"], len(configs))):
        if len(group) != n:
            bad.append("two entries share a name")
    if set(e2e) & set(layer):
        bad.append(f"metrics in both lists: {sorted(set(e2e) & set(layer))}")
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")

    for name, c in configs.items():
        name_ok("config", name)
        line_ok(f"config {name} source", c.get("source"))
        line_ok(f"config {name} why", c.get("why"))
        if sorted(c) != ["file", "name", "reduced", "source", "why"]:
            bad.append(f"config {name}: keys {sorted(c)}")
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {name}: no file {c['file']}")
        elif sorted(load_json(root, c["file"]).get("reduced", [])) != sorted(
                c["reduced"]):
            bad.append(f"config {name}: `reduced` differs from its file's")
        for key in c["reduced"]:
            name_ok(f"config {name} reduced", key)
        if not any(w["config"] == name for w in cells.values()):
            bad.append(f"config {name}: no cell uses it")
    if len({c["file"] for c in configs.values()}) != len(configs):
        bad.append("two configurations share a file")

    pairs = set()
    for name, w in cells.items():
        name_ok("workload", name)
        name_ok(f"workload {name} traffic", w.get("traffic"))
        line_ok(f"workload {name} why", w.get("why"))
        if sorted(w) != ["chips", "config", "name", "traffic", "why"]:
            bad.append(f"workload {name}: keys {sorted(w)}")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {name}: chips {w['chips']}")
        if w["config"] not in configs:
            bad.append(f"workload {name}: unknown config {w['config']}")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {name}: its config and traffic pair twice")
        pairs.add((w["config"], w["traffic"]))
        files = {"workload": os.path.join(bench, "workloads", name + ".json"),
                 "traffic": os.path.join(bench, "traffic",
                                         w["traffic"] + ".json")}
        missing = [k for k, p in files.items() if not os.path.isfile(p)]
        if missing:
            bad.append(f"workload {name}: no {' or '.join(missing)} file")
            continue
        wl = load_json(files["workload"])
        if not os.path.isfile(os.path.join(bench, "drivers",
                                           wl["driver"] + ".py")):
            bad.append(f"workload {name}: no driver {wl['driver']}")
        mine_e2e, mine_layer = set(wl["end_to_end"]), set(wl["per_layer"])
        if "setup_s" not in mine_e2e or len(mine_e2e) < 2 or not mine_layer:
            bad.append(f"workload {name}: needs setup_s, one more "
                       "end-to-end metric and one per-layer metric")
        for n in sorted(mine_e2e | mine_layer):
            listed = e2e if n in mine_e2e else layer
            path = os.path.join(bench, "metrics", n + ".json")
            if n not in listed:
                bad.append(f"workload {name}: metric {n} is not in "
                           "BENCHMARK.json's list of its kind")
                continue
            if "workloads" in listed[n] and name not in listed[n]["workloads"]:
                bad.append(f"metric {n}: BENCHMARK.json does not list "
                           f"workload {name}, which reports it")
            if not os.path.isfile(path):
                bad.append(f"metric {n}: no file metrics/{n}.json")
                continue
            spec = load_json(path)
            if not os.path.isfile(os.path.join(bench, "reducers",
                                               spec["reducer"] + ".py")):
                bad.append(f"metric {n}: no reducer {spec['reducer']}")
            for key in ("unit", "better", "source", "layer", "moves"):
                if key in listed[n] and spec.get(key) != listed[n][key]:
                    bad.append(f"metric {n}: {key} differs between "
                               "BENCHMARK.json and its file")
            if n in mine_layer and listed[n].get("moves") not in mine_e2e:
                bad.append(f"workload {name}: {n} moves "
                           f"{listed[n].get('moves')}, which the cell does "
                           "not report")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")

    for n, x in {**e2e, **layer}.items():
        name_ok("metric", n)
        if not UNIT.match(str(x.get("unit", ""))):
            bad.append(f"metric {n}: unit {x.get('unit')!r}")
        if x.get("better") not in ("lower", "higher"):
            bad.append(f"metric {n}: better {x.get('better')!r}")
        if x.get("source") not in SOURCES:
            bad.append(f"metric {n}: source {x.get('source')!r}")
        for cell_name in x.get("workloads", []):
            wl_path = os.path.join(bench, "workloads", cell_name + ".json")
            if cell_name not in cells:
                bad.append(f"metric {n}: lists unknown workload {cell_name}")
            elif os.path.isfile(wl_path):
                wl = load_json(wl_path)
                if n not in wl["end_to_end"] and n not in wl["per_layer"]:
                    bad.append(f"metric {n}: lists workload {cell_name}, "
                               "whose file does not report it")
        extra = set(x) - {"name", "unit", "better", "source", "workloads",
                          "bound", "layer", "moves"}
        if extra:
            bad.append(f"metric {n}: keys {sorted(extra)}")
    for n, x in e2e.items():
        if x["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end metric {n}: source {x['source']}")
        if not 0 < x.get("bound", 0) <= 0.1:
            bad.append(f"end-to-end metric {n}: bound {x.get('bound')}")
    for n, x in layer.items():
        line_ok(f"metric {n} layer", x.get("layer"))
        if x.get("moves") not in e2e:
            bad.append(f"metric {n}: moves {x.get('moves')!r}, not an "
                       "end-to-end metric")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: counts and `correct` only")
    ap.add_argument("--check", action="store_true",
                    help="validate BENCHMARK.json against the files")
    args = ap.parse_args(argv)
    if args.check:
        bad = check(os.path.join(ROOT, "BENCHMARK.json"))
        for line in bad:
            print("check:", line)
        print(json.dumps({"check": "ok" if not bad else "failed",
                          "problems": len(bad)}))
        return 1 if bad else 0
    if not args.workload:
        ap.error("--workload is required")
    if args.seconds is None and not args.rehearse:
        ap.error("--seconds is required")
    return run(args)


if __name__ == "__main__":
    sys.path.insert(0, BENCH)      # drivers, reducers, lib
    sys.path.insert(0, ROOT)       # the program: mmlspark_tpu
    sys.exit(main())
