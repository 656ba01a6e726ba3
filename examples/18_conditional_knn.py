"""ConditionalKNN: exploring art across cultures.

Reference workload: "ConditionalKNN - Exploring Art Across Cultures.ipynb"
— given a query artwork's feature vector, find its nearest neighbors
RESTRICTED to chosen cultures/media (the conditioner set), so "show me
the closest *Egyptian* pieces to this Greek vase" is one query instead
of a full KNN + post-filter (core nn/ConditionalKNN.scala, ball-tree
with label masks pushed into the search).  Matching follows the
reference's BallTree semantics: maximum INNER PRODUCT, the "distance"
each BestMatch carries.

Synthetic museum: per-culture style clusters in feature space, queried
under different conditioners.  The conditioner provably constrains
results AND the scores are exact (checked against brute force).

Run: python examples/18_conditional_knn.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.nn import ConditionalKNN

CULTURES = ["greek", "egyptian", "japanese", "maya"]
FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))


def main():
    rng = np.random.default_rng(8)
    per = 20 if FAST else 60
    d = 16
    centers = rng.normal(size=(len(CULTURES), d)) * 3.0
    feats, culture, titles = [], [], []
    for ci, c in enumerate(CULTURES):
        feats.append(centers[ci] + rng.normal(size=(per, d)))
        culture += [c] * per
        titles += [f"{c}-artwork-{i}" for i in range(per)]
    x = np.concatenate(feats).astype(np.float32)
    index = Table({"features": x, "values": titles, "labels": culture,
                   "conditioner": [{c} for c in culture]})
    model = ConditionalKNN(k=4, label_col="labels").fit(index)

    # a query near the GREEK cluster, searched under different conditioners
    q = (centers[0] + rng.normal(size=d) * 0.5).astype(np.float32)
    for cond in ({"greek"}, {"egyptian"}, {"greek", "japanese"}):
        out = model.transform(Table({
            "features": q[None, :], "conditioner": [cond]}))["output"][0]
        got = [(m["value"], m["label"], round(float(m["distance"]), 2))
               for m in out]
        print(f"conditioner={sorted(cond)}: {got}")
        assert all(m["label"] in cond for m in out), got
        # exactness vs brute force (max inner product) under the same mask
        mask = np.asarray([c in cond for c in culture])
        brute = np.sort(x[mask] @ q)[-4:][::-1]
        np.testing.assert_allclose(
            [m["distance"] for m in out], brute, rtol=1e-5)
    print("conditioner respected and scores match brute-force MIPS")


if __name__ == "__main__":
    main()
