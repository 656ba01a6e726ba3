"""Text analytics: book-review sentiment, bag-of-words vs Word2Vec.

Reference workloads: "TextAnalytics - Amazon Book Reviews.ipynb" (hashed
TF features + TrainClassifier) and "TextAnalytics - Amazon Book Reviews
with Word2Vec.ipynb" (SparkML Word2Vec doc vectors + the same trainer).
The Amazon data is an external download; a synthetic review corpus with
the same shape (free text, 1-5 star ratings binarized at >3) stands in.

Both recipes run side by side, exactly like the two notebooks:
TextFeaturizer (hashed TF-IDF) vs Word2Vec mean-of-word-vectors into
the same LogisticRegression head, evaluated on held-out reviews; then
`find_synonyms` shows what the embedding space learned.

Run: python examples/23_text_analytics_word2vec.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.featurize import TextFeaturizer, Word2Vec
from mmlspark_tpu.models.linear import LogisticRegression

FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))

POS = ["wonderful", "gripping", "masterful", "delightful", "superb"]
NEG = ["tedious", "shallow", "clumsy", "dreadful", "forgettable"]
FILL = ["the", "book", "plot", "chapters", "author", "characters",
        "story", "prose", "pacing", "ending"]


def _reviews(rng, n):
    texts, stars = [], []
    for _ in range(n):
        rating = int(rng.integers(1, 6))
        lex = POS if rating > 3 else NEG
        words = list(rng.choice(FILL, size=7))
        for _k in range(2):
            words.insert(int(rng.integers(len(words))),
                         str(rng.choice(lex)))
        texts.append(" ".join(words))
        stars.append(rating)
    return texts, np.asarray(stars)


def main():
    rng = np.random.default_rng(2)
    n = 200 if FAST else 800
    texts, stars = _reviews(rng, n)
    labels = (stars > 3).astype(np.float64)       # the notebooks' binarize
    cut = int(n * 0.75)

    def evaluate(name, train_feats, test_feats):
        t = Table({"features": train_feats, "label": labels[:cut]})
        clf = LogisticRegression(max_iter=150).fit(t)
        pred = np.asarray(clf.transform(
            Table({"features": test_feats}))["prediction"])
        acc = float(np.mean(pred == labels[cut:]))
        print(f"{name}: held-out accuracy {acc:.3f}")
        return acc

    # recipe 1: hashed TF-IDF (TextAnalytics - Amazon Book Reviews)
    tf = TextFeaturizer(input_col="text", output_col="features",
                        num_features=512).fit(Table({"text": texts[:cut]}))
    acc_tf = evaluate(
        "hashed TF-IDF + logistic",
        tf.transform(Table({"text": texts[:cut]}))["features"],
        tf.transform(Table({"text": texts[cut:]}))["features"])

    # recipe 2: Word2Vec doc vectors (... with Word2Vec)
    w2v = Word2Vec(input_col="text", output_col="features",
                   vector_size=16, window_size=3, min_count=2,
                   epochs=3 if FAST else 6, seed=1).fit(
        Table({"text": texts[:cut]}))
    acc_w2v = evaluate(
        "word2vec mean-vectors + logistic",
        np.asarray(w2v.transform(Table({"text": texts[:cut]}))["features"]),
        np.asarray(w2v.transform(Table({"text": texts[cut:]}))["features"]))

    print(f"synonyms('superb'): "
          f"{[w for w, _ in w2v.find_synonyms('superb', 4)]}")
    assert acc_tf > 0.85 and acc_w2v > 0.85
    # the embedding clusters the sentiment lexicon it was never told about
    syn = [w for w, _ in w2v.find_synonyms("superb", 4)]
    assert sum(w in POS for w in syn) >= 2, syn
    print("both notebook recipes reproduced; embeddings cluster sentiment")


if __name__ == "__main__":
    main()
