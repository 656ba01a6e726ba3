"""Text interpretability: token-level LIME and SHAP over a trained model.

Reference workload: "Interpretability - Text Explainers.ipynb" — explain
a sentiment classifier's score token by token (TextLIME/TextSHAP with
bernoulli keep-masks / coalition sampling).

The explained model is trained, not scripted: TextFeaturizer (hashed
bag-of-words) + logistic head on a tiny synthetic sentiment corpus where
"superb"/"awful" carry the signal.  The explainers recover exactly those
tokens as the attribution leaders without knowing the vocabulary.

Run: python examples/16_text_explainers.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.core.pipeline import LambdaTransformer
from mmlspark_tpu.explainers import TextLIME, TextSHAP
from mmlspark_tpu.featurize.text import TextFeaturizer
from mmlspark_tpu.models.linear import LogisticRegression

FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))

GOOD = ["superb", "great", "lovely"]
BAD = ["awful", "dire", "boring"]
FILLER = ["the", "film", "was", "plot", "acting", "overall", "scenes"]


def _corpus(rng, n):
    texts, labels = [], []
    for i in range(n):
        pos = i % 2 == 0
        words = list(rng.choice(FILLER, size=5))
        words.insert(int(rng.integers(5)),
                     str(rng.choice(GOOD if pos else BAD)))
        texts.append(" ".join(words))
        labels.append(float(pos))
    return texts, np.asarray(labels)


def main():
    rng = np.random.default_rng(1)
    texts, labels = _corpus(rng, 60 if FAST else 160)
    feat = TextFeaturizer(input_col="text", output_col="features",
                          num_features=256).fit(
        Table({"text": texts}))
    head = LogisticRegression(max_iter=300).fit(
        feat.transform(Table({"text": texts})).with_column("label", labels))

    def scored(t):
        probs = head.transform(feat.transform(t))["scores"]
        return t.with_column("scores", np.asarray(probs)[:, 1])

    review = "the film was superb overall but the plot was boring"
    t = Table({"text": [review]})
    print(f"explaining: {review!r} "
          f"(P(positive)={scored(t)['scores'][0]:.3f})")
    for name, cls in (("TextLIME", TextLIME), ("TextSHAP", TextSHAP)):
        out = cls(model=LambdaTransformer(scored),
                  num_samples=96 if FAST else 256, seed=4).transform(t)
        toks = out["tokens"][0]
        coefs = np.asarray(out["explanation"][0])[0][: len(toks)]
        order = np.argsort(-coefs)
        ranked = [(toks[j], round(float(coefs[j]), 3)) for j in order]
        print(f"{name}: {ranked[:3]} ... {ranked[-2:]}")
        assert toks[order[0]] == "superb", ranked
        assert toks[int(np.argmin(coefs))] == "boring", ranked
    print("both explainers rank 'superb' highest and 'boring' lowest")


if __name__ == "__main__":
    main()
