"""Tabular interpretability: KernelSHAP over a trained GBDT.

Reference workload: "Interpretability - Tabular SHAP explainer.ipynb" —
train a classifier on tabular rows, then explain individual predictions
with per-feature SHAP values (cognitive churn there; breast-cancer here,
the dataset bundled with this image).

The pipeline is the reference's shape: fit GBDT -> wrap its probability
as the explained score -> TabularSHAP samples feature coalitions around
each instance against the background mean, solves the kernel-weighted
regression, and emits per-feature attributions whose SUM reproduces
f(x) - f(background) (additivity — checked below, not just narrated).

Run: python examples/14_tabular_shap_interpretability.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.core.pipeline import LambdaTransformer
from mmlspark_tpu.explainers import TabularSHAP
from mmlspark_tpu.gbdt import GBDTClassifier

FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))


def main():
    from sklearn.datasets import load_breast_cancer

    d = load_breast_cancer()
    n = 120 if FAST else len(d.data)
    table = Table({"features": d.data[:n].astype(np.float64),
                   "label": d.target[:n].astype(np.float64)})
    model = GBDTClassifier(num_iterations=20 if FAST else 60,
                           num_leaves=15, min_data_in_leaf=10,
                           seed=0).fit(table)

    def scored(t):  # the explained function: P(malignant=0 class 1)
        return t.with_column(
            "scores", np.asarray(model.transform(t)["probability"])[:, 1])

    explain_rows = Table({"features": d.data[:4].astype(np.float64)})
    shap = TabularSHAP(model=LambdaTransformer(scored),
                       num_samples=64 if FAST else 256, seed=7,
                       background_data=table)
    out = shap.transform(explain_rows)

    base = scored(Table({"features": d.data[:n].mean(
        axis=0, keepdims=True)}))["scores"][0]
    for i in range(len(explain_rows)):
        phi = np.asarray(out["explanation"][i])[0]
        fx = scored(Table({"features": d.data[i:i + 1]}))["scores"][0]
        top = np.argsort(-np.abs(phi))[:3]
        print(f"row {i}: f(x)={fx:.3f} base={base:.3f} "
              f"sum(phi)={phi.sum():+.3f} top features: "
              + ", ".join(f"{d.feature_names[j]} ({phi[j]:+.3f})"
                          for j in top))
        # additivity within sampling tolerance — SHAP's defining property
        assert abs(phi.sum() - (fx - base)) < 0.25, (phi.sum(), fx, base)
    print("tabular SHAP additivity holds on all explained rows")


if __name__ == "__main__":
    main()
