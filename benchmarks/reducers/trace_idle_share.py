"""Share of the traced slice in which no operation ran on the device, in
percent: 1 - union of op intervals / slice, the mean over the devices.
The worst device goes on an earlier line."""
import json

from lib import trace as tr


def reduce(ctx):
    if ctx.trace is None or not ctx.slice.get("seconds"):
        return None
    window = ctx.slice["t1"] - ctx.slice["t0"]
    idle = {dev: 100.0 * (1.0 - busy / window)
            for dev, busy in tr.busy_seconds(ctx.trace).items()}
    print(json.dumps({"line": "idle_by_device", "idle_pct": idle,
                      "worst": max(idle.values())}), flush=True)
    return sum(idle.values()) / len(idle)
