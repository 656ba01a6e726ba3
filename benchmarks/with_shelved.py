"""A copy of the benchmark with the entries of a `.shelved.json` merged in.

    python3 benchmarks/with_shelved.py <file.shelved.json> <dir>
    PYTHONPATH=. python3 <dir>/benchmarks/run.py --workload <cell> ...

A `.shelved.json` holds entries for BENCHMARK.json that are built and not
admitted (its `why` says what stands in the way).  This writes `<dir>/
BENCHMARK.json` and `<dir>/benchmarks/` as a PR that may admit them would
leave them: the entries appended to their lists, and every cell a metric
lists given the key `<metric>: null` in its `workloads/<cell>.json`.  The
copy's `run.py` finds the program through PYTHONPATH.  Nothing the driver
runs reads the copy: it is for a builder's own traced runs and the tests.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def merge(shelved_path: str, into: str) -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(shelved_path) as f:
        shelved = json.load(f)
    bench = os.path.join(into, "benchmarks")
    shutil.copytree(BENCH, bench, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    for key in LISTS:
        have = {x["name"]: x for x in manifest[key]}
        for x in shelved.get(key, []):
            if x["name"] in have:
                have[x["name"]].setdefault("workloads", []).extend(
                    x["workloads"])
            else:
                manifest[key].append(x)
    for key in ("end_to_end", "per_layer"):
        for x in shelved.get(key, []):
            for cell in x.get("workloads", []):
                path = os.path.join(bench, "workloads", cell + ".json")
                with open(path) as f:
                    workload = json.load(f)
                workload[key].setdefault(x["name"], None)
                with open(path, "w") as f:
                    json.dump(workload, f, indent=1)
    with open(os.path.join(into, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)


if __name__ == "__main__":
    if len(sys.argv) != 3:
        sys.exit(__doc__.split("\n\n")[1])
    merge(sys.argv[1], sys.argv[2])
