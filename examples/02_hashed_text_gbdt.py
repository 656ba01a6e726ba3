"""High-dimensional text classification: hashed features -> sparse GBDT.

The hashed (indices, values) column flows straight into the CSR dataset
path — 2^18 feature dimensions with no dense materialization (the
reference's LightGBM sparse DatasetAggregator scenario).

Run: python examples/02_hashed_text_gbdt.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Pipeline, Table
from mmlspark_tpu.gbdt import GBDTClassifier, SparseBinMapper
from mmlspark_tpu.models.statistics import roc_auc
from mmlspark_tpu.online import VowpalWabbitFeaturizer


def synthetic_reviews(n=1200, seed=0):
    rng = np.random.default_rng(seed)
    good = [f"great{i}" for i in range(25)]
    bad = [f"awful{i}" for i in range(25)]
    filler = [f"word{i}" for i in range(400)]
    texts, labels = [], []
    for _ in range(n):
        label = int(rng.random() < 0.5)
        words = list(rng.choice(good if label else bad, 3)) + \
            list(rng.choice(filler, 10))
        rng.shuffle(words)
        texts.append(" ".join(words))
        labels.append(float(label))
    return Table({"text": np.asarray(texts, object),
                  "label": np.asarray(labels)})


def main():
    table = synthetic_reviews()
    pipe = Pipeline(stages=[
        VowpalWabbitFeaturizer(input_cols=["text"], output_col="features",
                               num_bits=18, string_split_cols=["text"]),
        # serial here so the demo is quick on a laptop CPU; on a TPU host
        # switch parallelism="data_parallel" to psum histograms over ICI
        GBDTClassifier(num_iterations=12, num_leaves=7, min_data_in_leaf=10,
                       max_bin=15, parallelism="serial"),
    ])
    model = pipe.fit(table)
    gbdt = model.stages[1]
    assert isinstance(gbdt.booster.bin_mapper, SparseBinMapper)
    print("trained sparse over", gbdt.booster.bin_mapper.num_features_,
          "hashed dims; nnz-only memory")
    out = model.transform(table)
    print("train AUC:", round(roc_auc(np.asarray(table["label"]),
                                      out["probability"][:, 1]), 4))


if __name__ == "__main__":
    main()
