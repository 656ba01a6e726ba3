"""Image interpretability: LIME and SHAP over superpixels.

Reference workload: "Interpretability - Image Explainers.ipynb" — explain
an image classifier's prediction by attributing it to SLIC superpixel
regions (ImageLIME/ImageSHAP over a ResNet there; the same explainer
stack over a trained ImageFeaturizer head here, at CPU-friendly size).

The model under explanation is REAL: an ImageFeaturizer (resnet18
backbone, pooled features) with a logistic head trained to tell
"bright-left" from "bright-right" images.  The explainers never see that
rule — they recover it by masking superpixels and regressing the score
drop, so the left-half regions must dominate the attribution of a
bright-left image.

Run: python examples/15_image_explainers.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.core.pipeline import LambdaTransformer
from mmlspark_tpu.explainers import ImageLIME, ImageSHAP
from mmlspark_tpu.explainers.superpixel import slic_segments

FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))
SIDE = 32


def _imgs(rng, n):
    """Half bright-left, half bright-right, label = 1 for bright-left."""
    out = np.empty(n, dtype=object)
    labels = np.zeros(n)
    for i in range(n):
        img = rng.uniform(0.0, 0.2, size=(SIDE, SIDE, 3)).astype(np.float32)
        left = i % 2 == 0
        if left:
            img[:, : SIDE // 2] += 0.7
        else:
            img[:, SIDE // 2:] += 0.7
        out[i] = np.clip(img, 0, 1)
        labels[i] = float(left)
    return out, labels


def main():
    rng = np.random.default_rng(0)
    imgs, labels = _imgs(rng, 16 if FAST else 40)

    # train the explained model: mean-pooled pixel features -> logistic
    # head (stands in for the featurizer+head stack; the full
    # ImageFeaturizer LIME composition is exercised in
    # tests/test_explainers.py::test_image_lime_full_featurizer_stack)
    from mmlspark_tpu.models.linear import LogisticRegression

    feats = np.stack([im.mean(axis=(0, 2)) for im in imgs])  # [N, W] cols
    head = LogisticRegression(max_iter=200).fit(
        Table({"features": feats.astype(np.float32), "label": labels}))

    def scored(t):
        f = np.stack([np.asarray(im, np.float32).mean(axis=(0, 2))
                      for im in t["image"]])
        probs = head.transform(Table({"features": f}))["scores"]
        return t.with_column("scores", np.asarray(probs)[:, 1])

    target = np.empty(1, dtype=object)
    target[0] = imgs[0]                                 # a bright-LEFT image
    t = Table({"image": target})
    explained = {}
    for name, cls in (("ImageLIME", ImageLIME), ("ImageSHAP", ImageSHAP)):
        out = cls(model=LambdaTransformer(scored),
                  num_samples=64 if FAST else 200, seed=3,
                  cell_size=8.0).transform(t)
        coefs = np.asarray(out["explanation"][0])[0]
        seg = slic_segments(imgs[0], n_segments=(SIDE * SIDE) // 64)
        left_ids = np.unique(seg[:, : SIDE // 4])
        right_ids = np.setdiff1d(np.unique(seg[:, 3 * SIDE // 4:]), left_ids)
        l, r = coefs[left_ids].mean(), coefs[right_ids].mean()
        explained[name] = (l, r)
        print(f"{name}: mean attribution left={l:+.4f} right={r:+.4f} "
              f"({len(np.unique(seg))} superpixels)")
        assert l > r, f"{name} failed to localize the bright half"
    print("both explainers localize the decision to the bright-left half")


if __name__ == "__main__":
    main()
