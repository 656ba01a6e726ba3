"""End-to-end backbone fine-tuning with checkpoint/resume.

DeepVisionClassifier trains a ResNet directly on (image, label) rows —
data-parallel over the device mesh, one jitted step per batch — and saves
an orbax checkpoint per epoch so an interrupted fit resumes where it
stopped.  (Beyond the reference: MMLSpark's training story stops at
featurize-then-linear-model.)

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python examples/05_finetune_vision.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.models.deep_vision import DeepVisionClassifier


def two_class_images(n=48, seed=0):
    rng = np.random.default_rng(seed)
    rows = np.empty(n, object)
    labels = []
    for i in range(n):
        label = i % 2
        base = np.array([40, 40, 180] if label else [180, 40, 40], np.uint8)
        rows[i] = np.clip(rng.normal(base, 30, (32, 32, 3)), 0, 255).astype(np.uint8)
        labels.append("ship" if label else "truck")
    return Table({"image": rows, "label": np.asarray(labels, object)})


def main():
    # MMLSPARK_EXAMPLE_FAST=1 shrinks the run for smoke tests (CI)
    fast = os.environ.get("MMLSPARK_EXAMPLE_FAST") not in (None, "", "0")
    epochs = 1 if fast else 3
    table = two_class_images(n=16 if fast else 48)
    with tempfile.TemporaryDirectory() as ck:
        est = DeepVisionClassifier(backbone="resnet18", epochs=epochs,
                                   batch_size=16, learning_rate=0.05,
                                   checkpoint_dir=ck)
        model = est.fit(table)
        print("per-epoch loss:", [round(l, 4) for l in model.loss_history])

        scored = model.transform(table)
        acc = (scored["prediction"] == table["label"]).mean()
        print("train accuracy:", acc)

        # interrupted? the same checkpoint_dir resumes instead of restarting
        resumed = DeepVisionClassifier(backbone="resnet18", epochs=epochs + 1,
                                       batch_size=16, learning_rate=0.05,
                                       checkpoint_dir=ck).fit(table)
        print("resume trained", len(resumed.loss_history),
              "additional epoch(s)")


if __name__ == "__main__":
    main()
