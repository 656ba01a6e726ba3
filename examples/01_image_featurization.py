"""Transfer-learning image featurization + classifier (the reference's
"DeepLearning - Flower Image Classification" notebook shape).

JPEG bytes -> ImageFeaturizer (ResNet backbone, pooled features) ->
TrainClassifier.  CPU-safe on synthetic data; on a TPU host the featurizer's
resize/normalize/forward runs as one fused device program.

Run: python examples/01_image_featurization.py
"""
import io
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
from PIL import Image

import jax.numpy as jnp

from mmlspark_tpu import Table
from mmlspark_tpu.models.bundle import FlaxBundle
from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
from mmlspark_tpu.models.train_classifier import TrainClassifier
from mmlspark_tpu.models.statistics import ComputeModelStatistics


def synthetic_flowers(n=64, seed=0):
    """Two 'species': bright-red-ish vs blue-ish noise JPEGs."""
    rng = np.random.default_rng(seed)
    blobs, labels = [], []
    for i in range(n):
        label = i % 2
        base = np.array([40, 40, 170] if label else [170, 40, 40])
        arr = np.clip(rng.normal(base, 40, size=(64, 64, 3)), 0, 255)
        buf = io.BytesIO()
        Image.fromarray(arr.astype(np.uint8)).save(buf, format="JPEG")
        blobs.append(buf.getvalue())
        labels.append(float(label))
    return Table({"image": blobs, "label": np.asarray(labels)})


def main():
    table = synthetic_flowers()
    bundle = FlaxBundle("resnet18", {"num_classes": 10, "dtype": jnp.float32},
                        input_shape=(32, 32, 3), seed=0)
    featurizer = ImageFeaturizer(bundle=bundle, cut_output_layers=1,
                                 batch_size=16)
    feats = featurizer.transform(table)
    print("features:", feats["features"].shape)

    train = Table({"f": feats["features"], "label": feats["label"]})
    model = TrainClassifier().fit(train)
    scored = model.transform(train)
    stats = ComputeModelStatistics(evaluation_metric="classification")
    out = stats.transform(scored)
    print({c: out[c][0] for c in out.column_names if c != "confusion_matrix"})


if __name__ == "__main__":
    main()
