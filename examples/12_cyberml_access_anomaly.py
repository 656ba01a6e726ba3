"""CyberML: unsupervised access-anomaly detection end to end.

The reference's CyberML workload (core cyber/ml — CF-based
AccessAnomaly over user->resource access logs; its AccessAnomaly
notebook walkthrough): raw string logs -> per-tenant id indexing ->
ALS-embedding fit (complement-weighted, the sparse sweep runs jitted on
device) -> standardized anomaly scores, where a user touching a resource
far from their usage cluster scores high.

Synthetic org: three departments whose users overwhelmingly access their
own department's resources, plus a few cross-department probes we expect
to light up.

Run: python examples/12_cyberml_access_anomaly.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.cyber.access_anomaly import AccessAnomaly
from mmlspark_tpu.cyber.feature import IdIndexer

DEPTS = ["eng", "sales", "hr"]
FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))


def synth_access_log(rng, users_per=8, res_per=10, events=1200):
    """(user, resource) event strings: 95% in-department, 5% noise."""
    users, ress = [], []
    for _ in range(events):
        d = rng.integers(len(DEPTS))
        u = f"{DEPTS[d]}-user{rng.integers(users_per)}"
        if rng.random() < 0.95:
            r = f"{DEPTS[d]}-doc{rng.integers(res_per)}"
        else:
            d2 = rng.integers(len(DEPTS))
            r = f"{DEPTS[d2]}-doc{rng.integers(res_per)}"
        users.append(u)
        ress.append(r)
    return Table({"user_id": np.asarray(users, object),
                  "res_id": np.asarray(ress, object)})


def main():
    rng = np.random.default_rng(7)
    log = synth_access_log(rng, events=400 if FAST else 1200)

    # raw strings -> contiguous indices (the reference's IdIndexer step)
    user_ix = IdIndexer(input_col="user_id", output_col="user").fit(log)
    res_ix = IdIndexer(input_col="res_id", output_col="res").fit(log)
    indexed = res_ix.transform(user_ix.transform(log))

    model = AccessAnomaly(rank=6, max_iter=6 if FAST else 10,
                          seed=0).fit(indexed)

    # score normal vs probe accesses through the SAME indexers; "normal"
    # = the log's most frequent (user, resource) pairs, "probe" = those
    # same users touching another department's resources
    from collections import Counter

    top = Counter(zip(log["user_id"], log["res_id"])).most_common(4)
    norm_pairs = [p for p, _n in top]
    normal = Table({
        "user_id": np.asarray([u for u, _ in norm_pairs], object),
        "res_id": np.asarray([r for _, r in norm_pairs], object)})
    other = {"eng": "hr", "sales": "eng", "hr": "sales"}
    probes = Table({
        "user_id": normal["user_id"],
        "res_id": np.asarray(
            [f"{other[u.split('-')[0]]}-doc{i}"
             for i, (u, _) in enumerate(norm_pairs)], object)})
    score = lambda t: model.transform(
        res_ix.transform(user_ix.transform(t)))["anomaly_score"]
    s_norm, s_probe = score(normal), score(probes)

    for tag, who, s in (("normal", normal, s_norm), ("probe", probes, s_probe)):
        for i in range(len(s)):
            print(f"{tag}: {who['user_id'][i]} -> {who['res_id'][i]}: "
                  f"score {float(s[i]):+.2f}")
    assert float(np.mean(s_probe)) > float(np.mean(s_norm)), (
        "cross-department probes should out-score in-department accesses")
    print("access-anomaly e2e: cross-department probes flagged ok")


if __name__ == "__main__":
    main()
