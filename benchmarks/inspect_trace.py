"""Look at one trace by hand before writing patterns against it.

    python benchmarks/inspect_trace.py <jax.profiler log dir> [--save <path>] [--clip <seconds>]

Prints every plane and line of the newest `.xplane.pb` under the directory,
and per line the names that took most time with one event's stats, so that
the op-name patterns in benchmarks/metrics/*.json can be written from what
the trace really calls things.  `--save` writes the reduced `Trace` (what
the reducers read), clipped to its first `--clip` seconds, as gzipped JSON:
that is how the fixture under benchmarks/fixtures/ was recorded.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    from lib import trace as tr

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("log_dir")
    ap.add_argument("--save")
    ap.add_argument("--clip", type=float, default=0.25)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    path = tr.find_xplane(args.log_dir)
    print(json.dumps({"xplane": path, "bytes": os.path.getsize(path)}))
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            by_name: dict = {}
            n = 0
            for e in line.events:
                n += 1
                rec = by_name.setdefault(e.name, [0.0, 0, None])
                rec[0] += e.duration_ns * 1e-9
                rec[1] += 1
                if rec[2] is None:
                    rec[2] = {k: str(v)[:160] for k, v in dict(e.stats).items()}
            if not n:
                continue
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1][0])
            print(json.dumps({"plane": plane.name, "line": line.name,
                              "events": n, "names": len(by_name)}))
            for name, (sec, count, stats) in ranked[:args.top]:
                print(json.dumps({"  name": name[:120], "s": round(sec, 6),
                                  "n": count, "stats": stats}))
    if args.save:
        full = tr.load_xplane(path)
        t0, t1 = tr.window_of(full, "bench.trace_slice")
        clipped = full.clip(t0, min(t1, t0 + args.clip))
        clipped.save(args.save)
        print(json.dumps({"saved": args.save, "t0": t0, "t1": t0 + args.clip,
                          "device_events": {k: len(v) for k, v in
                                            clipped.devices.items()},
                          "host_events": len(clipped.host)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
