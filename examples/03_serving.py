"""Low-latency model serving with the readStream DSL.

Train a small model, serve it with continuous batching, POST to it, and
show the distributed multi-replica variant with service discovery
(the reference's "Spark Serving" quickstart, docs/mmlspark-serving.md)
fronted by the fleet gateway — one URL, registry-discovered replicas,
balanced routing (docs/serving.md).

Run: python examples/03_serving.py
"""
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.models.linear import LogisticRegression
from mmlspark_tpu.serving import (DistributedServingServer, FleetGateway,
                                  list_services, read_stream)


def post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


def main():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(400, 3)).astype(np.float32)
    y = (x[:, 0] - x[:, 1] > 0).astype(np.float64)
    model = LogisticRegression(max_iter=100).fit(
        Table({"features": x, "label": y}))

    def score(t: Table) -> Table:
        feats = np.stack([np.asarray(t[c], np.float32)
                          for c in ("f0", "f1", "f2")], axis=1)
        out = model.transform(Table({"features": feats}))
        return t.with_column("prediction", out["prediction"])

    query = (read_stream()
             .continuous_server(name="scorer", path="/score")
             .parse_request(schema=["f0", "f1", "f2"])
             .transform(score)
             .make_reply("prediction")
             .start())
    try:
        print("serving at", query.service_info.url)
        print("reply:", post(query.service_info.url,
                             {"f0": 2.0, "f1": -1.0, "f2": 0.0}))
    finally:
        query.stop()

    # distributed: 2 replicas + discovery registry, fronted by the fleet
    # gateway — clients see ONE url; the gateway discovers the replicas
    # from the registry and balances across them (docs/serving.md)
    from mmlspark_tpu.core.pipeline import LambdaTransformer

    dist = DistributedServingServer(
        model=LambdaTransformer(score), reply_col="prediction",
        name="scorer-fleet", path="/score", replicas=2)
    infos = dist.start()
    gw = FleetGateway(name="scorer-fleet", path="/score",
                      registry_url=dist.registry.url)
    try:
        print("replicas:", [i.url for i in infos])
        print("discovered:", len(list_services(dist.registry.url,
                                               "scorer-fleet")))
        gw_info = gw.start()
        print("gateway:", gw_info.url)
        for i in range(4):
            print(f"via gateway {i} ->",
                  post(gw_info.url, {"f0": -2.0, "f1": 1.0, "f2": 0.0}))
        forwarded = {r["url"]: r["forwarded"]
                     for r in gw.describe()["replicas"]}
        print("forwards per replica:", forwarded)
    finally:
        gw.stop()
        dist.stop()


if __name__ == "__main__":
    main()
