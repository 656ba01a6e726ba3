"""Vowpal Wabbit overview: hashing, online learning, interactions.

Reference workload: "Vowpal Wabbit - Overview.ipynb" — the VW toolchain
tour: hashed featurization of mixed columns, an online classifier with
adaptive (AdaGrad) updates over multiple passes, a regressor, quadratic
namespace interactions, and the per-pass performance statistics table.

Here the same surface runs TPU-native (vw/ package in the reference ->
online/ here): murmur3 hashing through the native C++ batch path,
learners as jitted AdaGrad sparse updates, interactions as hashed
feature crosses (SURVEY §2.8).

Run: python examples/20_vowpal_wabbit_overview.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.online import (
    VowpalWabbitClassifier,
    VowpalWabbitFeaturizer,
    VowpalWabbitInteractions,
    VowpalWabbitRegressor,
)

FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))


def _adult_like(rng, n):
    """Census-ish mixed rows: numeric age/hours, categorical job/edu."""
    jobs = ["clerk", "eng", "sales", "exec"]
    edus = ["hs", "college", "masters"]
    # unit-scale numerics: hashed features carry raw magnitudes, and an
    # online learner on unscaled age/hours spends its passes re-learning
    # the scale (the notebook's data prep does the same standardization)
    age = (rng.integers(18, 65, size=n) - 40.0) / 10.0
    hours = (rng.integers(20, 60, size=n) - 40.0) / 10.0
    job = rng.choice(jobs, size=n)
    edu = rng.choice(edus, size=n)
    score = (age + hours
             + (job == "exec") * 1.5 + (edu == "masters") * 1.0
             + rng.normal(size=n) * 0.3)
    return Table({"age": age, "hours": hours, "job": job, "edu": edu,
                  # "const" is VW's intercept: vw injects a Constant
                  # feature into every example; here it is an explicit
                  # all-ones column through the same hashed path
                  "const": np.ones(n),
                  "label": (score > 0).astype(np.float64),
                  "income": 30.0 + 10.0 * score})


def main():
    rng = np.random.default_rng(4)
    n = 300 if FAST else 1200
    t = _adult_like(rng, n)

    # 1. hashed featurization of mixed columns (VowpalWabbitFeaturizer)
    feat = VowpalWabbitFeaturizer(
        input_cols=["age", "hours", "job", "edu", "const"], num_bits=18)
    tf = feat.transform(t)
    ind, val = tf["features"][0]
    print(f"hashed features: {len(ind)} active slots (incl. intercept) in a "
          f"{1 << 18}-slot space (murmur3, native batch path)")

    # 2. online binary classifier, multiple passes, adaptive updates
    clf = VowpalWabbitClassifier(num_passes=3 if FAST else 6,
                                 learning_rate=0.5).fit(tf)
    acc = float(np.mean(np.asarray(clf.transform(tf)["prediction"])
                        == t["label"]))
    stats = clf.performance_statistics
    print(f"classifier accuracy {acc:.3f}; per-pass average loss: "
          f"{[round(float(l), 4) for l in stats['average_loss']]}")
    assert acc > 0.8
    assert stats["average_loss"][-1] < stats["average_loss"][0]

    # 3. regressor on the continuous target
    reg = VowpalWabbitRegressor(num_passes=3 if FAST else 6,
                                learning_rate=0.3,
                                label_col="income").fit(tf)
    pred = np.asarray(reg.transform(tf)["prediction"])
    rmse = float(np.sqrt(np.mean((pred - t["income"]) ** 2)))
    base = float(np.std(t["income"]))
    print(f"regressor RMSE {rmse:.2f} vs predict-the-mean {base:.2f}")
    assert rmse < base

    # 4. quadratic interactions (job x edu cross features)
    fj = VowpalWabbitFeaturizer(input_cols=["job"], output_col="fj",
                                num_bits=12)
    fe = VowpalWabbitFeaturizer(input_cols=["edu"], output_col="fe",
                                num_bits=12)
    crossed = VowpalWabbitInteractions(
        input_cols=["fj", "fe"], num_bits=12).transform(
        fe.transform(fj.transform(t)))
    ci, cv = crossed["interactions"][0]
    print(f"interactions: {len(ci)} crossed slot(s) per row "
          f"(|job| x |edu| hashes)")
    assert len(ci) == 1
    print("VW surface tour complete: hashing, online passes, "
          "regression, interactions")


if __name__ == "__main__":
    main()
