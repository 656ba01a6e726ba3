"""Batch featurization: a table of JPEG bytes through
`ImageFeaturizer.transform`, back to back, closed loop.

One unit of work is one whole `transform(table)`: native decode, the host
pipeline, the feed, the fused resize kernel and the backbone, host arrays
out.  The reference is the same featurizer on the XLA path
(`use_pallas=False`) under `highest` matmul precision, on sampled rows.
"""
from __future__ import annotations

import hashlib
import os
import time

# bf16 backbone against itself on another preprocessing path: PR 21 read
# 3.2e-4 on the chip; a wrong resize, a wrong mean or channel order, or a
# dropped row is far outside, bf16 rounding of 50 layers far inside
FEATURE_REL_TOL = 2e-2


def _native_ready(out_dir: str) -> None:
    """Build the native library from the files of this checkout: rebuilt
    when a hash of native/src differs from the stamp of the last build."""
    from mmlspark_tpu import native

    src = os.path.join(os.path.dirname(native.__file__), "src")
    digest = hashlib.sha256()
    for name in sorted(os.listdir(src)):
        with open(os.path.join(src, name), "rb") as f:
            digest.update(name.encode() + f.read())
    stamp = os.path.join(out_dir, "native.stamp")
    fresh = (os.path.isfile(stamp)
             and open(stamp).read() == digest.hexdigest())
    if not native.build(force=not fresh):
        raise RuntimeError("native library failed to build from "
                           "native/src")
    if not native.jpeg_available():
        raise RuntimeError("native library built without libjpeg: the "
                           "decode path this cell measures would not run")
    with open(stamp, "w") as f:
        f.write(digest.hexdigest())


def setup(env) -> dict:
    from lib.images import jpeg_blobs
    from mmlspark_tpu import Table
    from mmlspark_tpu.models.bundle import FlaxBundle
    from mmlspark_tpu.models.image_featurizer import ImageFeaturizer

    cfg, traffic = env.config, env.traffic
    _native_ready(env.out_dir)
    blobs = jpeg_blobs(traffic["images"], traffic["sizes"], env.seed,
                       coarse=traffic["coarse"], noise=traffic["noise"],
                       quality=traffic["quality"])
    raw = sum(h * w * 3 for i in range(len(blobs))
              for h, w in [traffic["sizes"][i % len(traffic["sizes"])]])
    env.log({"line": "images", "n": len(blobs),
             "mean_bytes": sum(map(len, blobs)) / len(blobs),
             "share_of_raw": sum(map(len, blobs)) / raw})
    side = cfg["image_size"]
    bundle = FlaxBundle(cfg["builder"], {"num_classes": cfg["num_classes"]},
                        input_shape=(side, side, 3), seed=env.seed)

    def featurizer(**kw):
        return ImageFeaturizer(bundle=bundle, input_col="image",
                               output_col="features",
                               batch_size=env.params["batch_size"], **kw)

    st = {"table": Table({"image": blobs}), "blobs": blobs,
          "feat": featurizer(), "featurizer": featurizer}
    # a FIXED number of whole passes, so that set-up is the same work in
    # every run: how the feed groups chunks into transfers depends on
    # arrival timing, and each grouping met for the first time is another
    # small program (the line below says how many each pass compiled)
    passes = []
    for _ in range(env.params["warm_passes"]):
        before = env.meter.count
        st["feat"].transform(st["table"])
        passes.append(env.meter.count - before)
    env.log({"line": "warmup", "compiles_per_pass": passes})
    return st


def measure(env, st) -> dict:
    import jax
    import numpy as np

    n = len(st["blobs"])
    done = {"images": 0}
    env.slice.open_window(lambda: done)
    work_s, calls, bad_rows, feats = 0.0, 0, 0, None
    deadline = time.monotonic() + env.seconds
    while time.monotonic() < deadline:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.transform"):
            feats = np.asarray(st["feat"].transform(st["table"])["features"])
        work_s += time.monotonic() - t0
        calls += 1
        done["images"] += n
        bad_rows += n - int(np.isfinite(feats).all(axis=1).sum()) \
            if feats.shape[0] == n else n
        env.slice.poll()
    st["last"] = feats
    return {"attempted": calls * n, "failed": bad_rows,
            "counters": {"images": float(calls * n), "window_s": work_s,
                         "transforms": float(calls)},
            "notes": {"transform_s_mean": work_s / calls}}


def verify(env, st, measured) -> dict:
    import jax
    import numpy as np

    from mmlspark_tpu import Table

    n = len(st["blobs"])
    rows = np.sort(np.random.default_rng(env.seed).choice(
        n, size=min(env.params["verify_rows"], n), replace=False))
    sub = Table({"image": [st["blobs"][i] for i in rows]})
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(st["featurizer"](use_pallas=False).transform(sub)
                         ["features"], np.float64)
    got = st["last"]
    dim = env.config["feature_dim"] or ref.shape[1]
    if got is None or got.shape != (n, dim):
        return {"correct": False, "why": f"features shape "
                f"{None if got is None else got.shape}"}
    got = np.asarray(got[rows], np.float64)
    diff = float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-12))
    return {"correct": bool(np.isfinite(got).all() and diff <= FEATURE_REL_TOL
                            and measured["failed"] == 0),
            "compared": "window features of sampled rows vs use_pallas=False "
                        "at highest precision (max abs diff over max abs ref)",
            "max_diff": diff, "tol": FEATURE_REL_TOL, "rows": len(rows)}


def close(st) -> None:
    pass
