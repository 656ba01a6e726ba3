"""Distributed training over a device mesh: data-parallel GBDT with
histogram psum, plus the online learner's end-of-pass AllReduce.

Runs anywhere: set XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu for a virtual 8-device mesh, or run on a TPU slice
unchanged (the mesh abstracts ICI/DCN placement).

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python examples/04_distributed_training.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

from mmlspark_tpu import Table
from mmlspark_tpu.gbdt import GBDTRegressor
from mmlspark_tpu.online import VowpalWabbitClassifier, VowpalWabbitFeaturizer
from mmlspark_tpu.parallel.mesh import make_mesh
from mmlspark_tpu.utils.cluster import device_topology


def main():
    topo = device_topology()
    print(f"topology: {len(topo.devices)} devices, {topo.num_hosts} host(s), "
          f"{topo.num_slices} slice(s)")
    mesh = make_mesh(data=len(jax.devices()))
    print("mesh:", dict(mesh.shape))

    rng = np.random.default_rng(0)
    x = rng.normal(size=(2000, 10))
    y = 2 * x[:, 0] + np.sin(x[:, 1] * 2) + 0.1 * rng.normal(size=2000)
    table = Table({"features": x.astype(np.float32), "label": y})

    # rows shard over the data axis; every histogram build is one psum
    model = GBDTRegressor(num_iterations=30, num_leaves=31,
                          parallelism="data_parallel").fit(table)
    pred = model.transform(table)["prediction"]
    print("GBDT data-parallel R^2:",
          round(1 - np.var(y - pred) / np.var(y), 4))

    # online learner: hashed features, pmean weight merge at end of pass
    t2 = Table({"a": x[:, 0], "b": x[:, 1],
                "label": (y > y.mean()).astype(np.float64)})
    feat = VowpalWabbitFeaturizer(input_cols=["a", "b"], num_bits=14)
    vw = VowpalWabbitClassifier(num_passes=4).fit(feat.transform(t2))
    acc = (vw.transform(feat.transform(t2))["prediction"]
           == t2["label"]).mean()
    print("VW distributed accuracy:", round(float(acc), 4))


if __name__ == "__main__":
    main()
