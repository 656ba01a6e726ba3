"""Regression with data cleaning: flight delays + auto imports.

Reference workloads: "Regression - Flight Delays with DataCleaning.ipynb"
and "Regression - Auto Imports.ipynb" — the tabular regression recipe:
raw rows with missing values and string categoricals -> CleanMissingData
-> Featurize (auto categorical/one-hot/passthrough) -> train ->
ComputeModelStatistics / ComputePerInstanceStatistics.

Both datasets are external downloads in the reference (flight CSVs, the
UCI auto-imports file); this image has no egress, so a structurally
faithful synthetic stands in for each: flight rows (carrier/origin
categoricals, NaN-holed numerics, delay target) and car rows
(make/fuel categoricals, engine-size numerics, price target).

Run: python examples/21_regression_flight_delays.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.featurize import CleanMissingData, Featurize
from mmlspark_tpu.gbdt import GBDTRegressor
from mmlspark_tpu.models.statistics import (
    ComputeModelStatistics,
    ComputePerInstanceStatistics,
)

FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))


def _flights(rng, n):
    carriers = ["AA", "DL", "UA", "WN"]
    origins = ["JFK", "ATL", "ORD", "SEA", "LAX"]
    carrier = rng.choice(carriers, size=n)
    origin = rng.choice(origins, size=n)
    dep_hour = rng.integers(5, 23, size=n).astype(np.float64)
    distance = rng.uniform(200, 2500, size=n)
    delay = (3.0 * (dep_hour - 12).clip(0)            # evening cascade
             + (carrier == "WN") * 8.0
             + (origin == "ORD") * 12.0
             + distance * 0.004 + rng.normal(size=n) * 5.0)
    # missing-data holes the cleaner must fill (reference: dropna/mean)
    dep_hour[rng.random(n) < 0.08] = np.nan
    distance[rng.random(n) < 0.05] = np.nan
    return Table({"carrier": carrier, "origin": origin,
                  "dep_hour": dep_hour, "distance": distance,
                  "label": delay})


def _autos(rng, n):
    makes = ["audi", "bmw", "honda", "mazda", "volvo"]
    fuel = rng.choice(["gas", "diesel"], size=n)
    make = rng.choice(makes, size=n)
    engine = rng.uniform(70, 300, size=n)
    weight = rng.uniform(1500, 4000, size=n)
    price = (engine * 60 + weight * 2
             + (make == "bmw") * 6000 + (make == "audi") * 4000
             + (fuel == "diesel") * 1500 + rng.normal(size=n) * 800)
    return Table({"make": make, "fuel": fuel, "engine_size": engine,
                  "curb_weight": weight, "label": price})


def _run(name, table, feature_cols):
    numeric = [c for c in feature_cols
               if np.issubdtype(np.asarray(table[c]).dtype, np.number)]
    clean = CleanMissingData(input_cols=numeric,
                             cleaning_mode="Mean").fit(table)
    cleaned = clean.transform(table)
    feat = Featurize(input_cols=feature_cols,
                     output_col="features").fit(cleaned)
    featurized = feat.transform(cleaned)
    model = GBDTRegressor(num_iterations=20 if FAST else 60,
                          num_leaves=15, min_data_in_leaf=10,
                          seed=0).fit(featurized)
    scored = model.transform(featurized)
    stats = ComputeModelStatistics(
        evaluation_metric="regression").transform(scored)
    r2 = float(stats["r2"][0])
    rmse = float(stats["rmse"][0])
    per = ComputePerInstanceStatistics(
        evaluation_metric="regression").transform(scored)
    worst = int(np.argmax(np.asarray(per["L2_loss"])))
    print(f"{name}: rmse={rmse:.2f} r2={r2:.3f}; worst row #{worst} "
          f"(L2 {float(per['L2_loss'][worst]):.1f})")
    assert r2 > 0.8, (name, r2)
    return r2


def _engine_shootout(table, feature_cols):
    """The "VW vs. LightGBM vs. Linear Regressor" notebook's three-way
    comparison — each engine with its native featurization (dense
    one-hot for GBDT/linear, hashed sparse for VW, like the notebook)."""
    from mmlspark_tpu.models.linear import LinearRegression
    from mmlspark_tpu.online import VowpalWabbitFeaturizer, VowpalWabbitRegressor

    numeric = [c for c in feature_cols
               if np.issubdtype(np.asarray(table[c]).dtype, np.number)]
    cleaned = CleanMissingData(input_cols=numeric,
                               cleaning_mode="Mean").fit(table).transform(table)
    featurized = Featurize(input_cols=feature_cols,
                           output_col="features").fit(cleaned).transform(cleaned)
    y = np.asarray(table["label"])
    vw_in = cleaned.with_column("const", np.ones(len(cleaned)))
    vw_feats = VowpalWabbitFeaturizer(
        input_cols=feature_cols + ["const"], num_bits=16).transform(vw_in)
    results = {}
    for name, est, data in (
            ("GBDT", GBDTRegressor(num_iterations=20 if FAST else 60,
                                   num_leaves=15, min_data_in_leaf=10),
             featurized),
            ("VowpalWabbit", VowpalWabbitRegressor(
                num_passes=4, learning_rate=0.3), vw_feats),
            ("Linear", LinearRegression(), featurized)):
        pred = np.asarray(est.fit(data).transform(data)["prediction"])
        results[name] = float(np.sqrt(np.mean((pred - y) ** 2)))
    for name, rmse in sorted(results.items(), key=lambda kv: kv[1]):
        print(f"  {name:<14} rmse {rmse:.2f}")
    assert results["GBDT"] < np.std(y)  # every engine beats the mean...
    return results


def main():
    rng = np.random.default_rng(6)
    n = 300 if FAST else 1500
    flights = _flights(rng, n)
    _run("flight delays", flights,
         ["carrier", "origin", "dep_hour", "distance"])
    _run("auto imports", _autos(rng, n),
         ["make", "fuel", "engine_size", "curb_weight"])
    print("engine shootout on flight delays (VW vs GBDT vs linear):")
    _engine_shootout(flights, ["carrier", "origin", "dep_hour", "distance"])
    print("clean -> featurize -> train -> statistics pipeline complete "
          "for both workloads, three regression engines compared")


if __name__ == "__main__":
    main()
