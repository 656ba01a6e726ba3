"""AutoML hyperparameter search: fighting breast cancer with k-fold CV.

Reference workload: "HyperParameterTuning - Fighting Breast Cancer.ipynb"
— TuneHyperparameters sweeps a random/grid space over candidate
estimators with cross-validation and hands back the best fitted model
(core automl/TuneHyperparameters.scala, HyperparamBuilder.scala).

Same dataset (Wisconsin breast cancer, bundled with sklearn), same
shape: two model families (logistic regression, GBDT) x a hyperparam
grid, 3-fold CV, accuracy metric, winner transforms new rows.

Run: python examples/17_hyperparameter_tuning.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.automl import (
    DiscreteHyperParam,
    GridSpace,
    HyperparamBuilder,
    TuneHyperparameters,
)
from mmlspark_tpu.gbdt import GBDTClassifier
from mmlspark_tpu.models.linear import LogisticRegression

FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))


def main():
    from sklearn.datasets import load_breast_cancer

    d = load_breast_cancer()
    n = 150 if FAST else len(d.data)
    # standardize: the logistic candidate competes on equal footing
    x = (d.data[:n] - d.data[:n].mean(0)) / (d.data[:n].std(0) + 1e-9)
    table = Table({"features": x.astype(np.float32),
                   "label": d.target[:n].astype(np.float64)})

    # learning_rate exists on BOTH candidate families (adam lr for the
    # logistic model, shrinkage for the GBDT), so one grid drives both —
    # the reference notebook's per-model builders collapse to this here
    space = (HyperparamBuilder()
             .add_hyperparam("learning_rate", DiscreteHyperParam([0.02, 0.2]))
             .build())
    candidates = [
        LogisticRegression(max_iter=100),
        GBDTClassifier(num_iterations=10 if FAST else 30, num_leaves=7,
                       min_data_in_leaf=10, seed=0),
    ]
    tuned = TuneHyperparameters(
        models=candidates, param_space=GridSpace(space),
        evaluation_metric="accuracy", num_folds=3,
        parallelism=2, seed=1,
    ).fit(table)

    print(f"trials: {len(tuned.all_metrics)} "
          f"(2 models x 2-point learning_rate grid, 3-fold CV)")
    for m in sorted(tuned.all_metrics, key=lambda m: -m["metric"]):
        print(f"  {m['estimator']:<22} {m['params']} -> CV accuracy "
              f"{m['metric']:.4f}")
    print(f"winner: CV accuracy {tuned.best_metric:.4f}")
    assert tuned.best_metric > 0.9

    scored = tuned.transform(table)
    acc = float(np.mean(np.asarray(scored["prediction"]) == table["label"]))
    print(f"best model train-set accuracy: {acc:.4f}")
    assert acc > 0.9


if __name__ == "__main__":
    main()
