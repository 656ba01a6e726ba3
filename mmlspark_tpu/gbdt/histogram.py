"""Jitted histogram builds and split finding — the GBDT hot loop on XLA.

Replaces the reference's native histogram kernels + socket-ring AllReduce
(LGBM_BoosterUpdateOneIter internals; ring built by LGBM_NetworkInit,
reference lightgbm/TrainUtils.scala:279-295).  A histogram build is a
`segment_sum` scatter-add over `feature*B + bin` ids; in data-parallel mode
the same program runs under `shard_map` with rows sharded over the mesh's
data axis and a single `psum` merging shard histograms over ICI.

Gain math follows LightGBM: for a split of a node with stats (G, H),
  gain = S(G_l,H_l) + S(G_r,H_r) - S(G,H),
  S(g,h) = T(g)^2 / (h + lambda_l2),  T(g) = soft-threshold of g by lambda_l1.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "build_histogram",
    "best_split",
    "SplitInfo",
    "HistogramBuilder",
]


class SplitInfo(NamedTuple):
    feature: int
    bin_threshold: int        # goes left if bin <= threshold
    gain: float
    left_grad: float
    left_hess: float
    left_count: float
    right_grad: float
    right_hess: float
    right_count: float


@partial(jax.jit, static_argnames=("num_bins",))
def build_histogram(binned, grad, hess, sample_weight, node_mask, num_bins):
    """[F, B, 3] histogram (grad, hess, count) of the rows where node_mask.

    binned: [N, F] uint8/int; grad/hess: [N] f32; sample_weight: [N] f32
    (bagging/goss weights, 0 = excluded); node_mask: [N] bool.
    """
    n, f = binned.shape
    w = sample_weight * node_mask.astype(grad.dtype)
    ids = binned.astype(jnp.int32) + jnp.arange(f, dtype=jnp.int32)[None, :] * num_bins
    ids = ids.reshape(-1)                                     # [N*F]
    stacked = jnp.stack([grad * w, hess * w, w], axis=1)      # [N, 3]
    vals = jnp.repeat(stacked[:, None, :], f, axis=1).reshape(-1, 3)
    hist = jax.ops.segment_sum(vals, ids, num_segments=f * num_bins)
    return hist.reshape(f, num_bins, 3)


@jax.jit
def subtract_histogram(parent, child):
    """Sibling histogram via subtraction — LightGBM's classic trick that
    halves histogram work (build only the smaller child)."""
    return parent - child


def _soft_threshold(g, l1):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


@partial(jax.jit, static_argnames=())
def _split_scores(hist, lambda_l1, lambda_l2, min_data_in_leaf, min_sum_hessian):
    """Per-(feature, bin-threshold) gain array [F, B]."""
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    c = hist[:, :, 2]
    gl = jnp.cumsum(g, axis=1)
    hl = jnp.cumsum(h, axis=1)
    cl = jnp.cumsum(c, axis=1)
    gt = gl[:, -1:]
    ht = hl[:, -1:]
    ct = cl[:, -1:]
    gr, hr, cr = gt - gl, ht - hl, ct - cl

    def leaf_score(gg, hh):
        t = _soft_threshold(gg, lambda_l1)
        return t * t / (hh + lambda_l2 + 1e-15)

    gain = leaf_score(gl, hl) + leaf_score(gr, hr) - leaf_score(gt, ht)
    valid = (
        (cl >= min_data_in_leaf)
        & (cr >= min_data_in_leaf)
        & (hl >= min_sum_hessian)
        & (hr >= min_sum_hessian)
    )
    return jnp.where(valid, gain, -jnp.inf)


@jax.jit
def _best_of(scores, feature_mask):
    masked = jnp.where(feature_mask[:, None], scores, -jnp.inf)
    flat = masked.reshape(-1)
    idx = jnp.argmax(flat)
    return idx, flat[idx]


@jax.jit
def _split_summary(hist, feature_mask, lambda_l1, lambda_l2,
                   min_data_in_leaf, min_sum_hessian):
    """One fused program per node: argmax split + its left/right stats as
    a single [8] vector — the grower pulls 32 bytes per node instead of
    the whole [F, B, 3] histogram plus separate scalar syncs (per-node
    host round trips dominate the grow loop otherwise)."""
    scores = _split_scores(hist, lambda_l1, lambda_l2, min_data_in_leaf,
                           min_sum_hessian)
    idx, gain = _best_of(scores, feature_mask)
    b = hist.shape[1]
    feat = idx // b
    thr = idx % b
    # gather the winning feature FIRST, then scan one [B, 3] row — O(B),
    # not a second full [F, B, 3] cumsum (F can be a 2^18 hash space)
    cs = jnp.cumsum(hist[feat], axis=0)
    left = cs[thr]
    right = cs[b - 1] - left
    # idx stays int32: float packing would corrupt splits once F*B > 2^24
    return idx.astype(jnp.int32), jnp.concatenate(
        [gain[None], left, right])


def best_split(
    hist: jax.Array,
    lambda_l1: float,
    lambda_l2: float,
    min_data_in_leaf: float,
    min_sum_hessian: float,
    min_gain: float,
    feature_mask: Optional[np.ndarray] = None,
) -> Optional[SplitInfo]:
    """Best (feature, bin) split of a node given its histogram, or None."""
    f, b, _ = hist.shape
    if feature_mask is None:
        feature_mask = np.ones(f, dtype=bool)
    idx, out = jax.device_get(_split_summary(
        hist, jnp.asarray(feature_mask), lambda_l1, lambda_l2,
        min_data_in_leaf, min_sum_hessian))
    gain = float(out[0])
    if not np.isfinite(gain) or gain <= min_gain:
        return None
    feat, thr = divmod(int(idx), b)
    return SplitInfo(
        feature=feat,
        bin_threshold=thr,
        gain=gain,
        left_grad=float(out[1]),
        left_hess=float(out[2]),
        left_count=float(out[3]),
        right_grad=float(out[4]),
        right_hess=float(out[5]),
        right_count=float(out[6]),
    )


class RowShardedBuilderBase:
    """Shared row-axis plumbing for the dense and sparse histogram builders:
    row padding to a shard multiple and mesh-aware placement of the per-row
    gradient/hessian/weight/mask arrays."""

    mesh = None
    axis = "data"
    _pad = 0

    def _pad_rows(self, arr, fill=0.0):
        if self._pad:
            pad_shape = (self._pad,) + arr.shape[1:]
            arr = np.concatenate([arr, np.full(pad_shape, fill, arr.dtype)])
        return arr

    def device_arrays(self, grad, hess, weight):
        """Place per-row arrays with the same row sharding as the data."""
        grad = self._pad_rows(np.asarray(grad, np.float32))
        hess = self._pad_rows(np.asarray(hess, np.float32))
        weight = self._pad_rows(np.asarray(weight, np.float32))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(self.mesh, P(self.axis))
            return (jax.device_put(grad, sh), jax.device_put(hess, sh),
                    jax.device_put(weight, sh))
        return jax.device_put(grad), jax.device_put(hess), jax.device_put(weight)

    def node_mask(self, mask: np.ndarray):
        mask = self._pad_rows(np.asarray(mask, bool), fill=False)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(mask, NamedSharding(self.mesh, P(self.axis)))
        return jax.device_put(mask)


class HistogramBuilder(RowShardedBuilderBase):
    """Owns device-resident binned data and builds per-node histograms.

    Single-chip path: one jitted segment_sum.  Distributed path
    (`mesh` given): rows are sharded over `axis` and per-shard histograms
    are `psum`'d — the ICI AllReduce standing in for LightGBM's TCP ring
    (reference lightgbm/LightGBMBase.scala:392-430).  Voting-parallel
    (`voting=True`) builds local histograms, selects top-k features by
    local gain on each shard, then only psums the union of voted features
    (params/LightGBMParams.scala:17 `voting_parallel`).
    """

    def __init__(
        self,
        binned: np.ndarray,
        num_bins: int,
        mesh: Optional["jax.sharding.Mesh"] = None,
        axis: str = "data",
        voting: bool = False,
        top_k: int = 20,
    ):
        self.num_bins = int(num_bins)
        self.mesh = mesh
        self.axis = axis
        self.voting = bool(voting)
        self.top_k = int(top_k)
        self.n, self.f = binned.shape
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            n_shards = mesh.shape[axis]
            pad = (-self.n) % n_shards
            if pad:
                binned = np.concatenate([binned, np.zeros((pad, self.f), binned.dtype)])
            self._pad = pad
            self.binned = jax.device_put(
                binned, NamedSharding(mesh, P(axis, None))
            )
            self._sharded_fn = self._make_sharded(mesh, axis)
            self._sharded_local_fn = self._make_sharded_local(mesh, axis)
        else:
            self._pad = 0
            self.binned = jax.device_put(np.ascontiguousarray(binned))
            self._sharded_fn = None
            self._sharded_local_fn = None

    def _make_sharded(self, mesh, axis):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import shard_map

        num_bins = self.num_bins

        def local_hist(binned, grad, hess, w, mask):
            h = build_histogram(binned, grad, hess, w, mask, num_bins)
            return jax.lax.psum(h, axis)

        fn = shard_map(
            local_hist,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(),
        )
        return jax.jit(fn)

    def build(self, grad, hess, weight, mask):
        """grad/hess/weight/mask: device arrays from device_arrays/node_mask."""
        if self._sharded_fn is not None:
            return self._sharded_fn(self.binned, grad, hess, weight, mask)
        return build_histogram(self.binned, grad, hess, weight, mask, self.num_bins)

    def build_local(self, grad, hess, weight, mask):
        """Per-shard histograms stacked on a leading shard axis [S, F, B, 3]
        (no collective) — the input to voting-parallel feature selection."""
        if self.mesh is None:
            h = build_histogram(self.binned, grad, hess, weight, mask, self.num_bins)
            return h[None]
        return self._sharded_local_fn(self.binned, grad, hess, weight, mask)

    def _make_sharded_local(self, mesh, axis):
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import shard_map

        num_bins = self.num_bins

        def local_hist(binned, grad, hess, w, mask):
            return build_histogram(binned, grad, hess, w, mask, num_bins)[None]

        fn = shard_map(
            local_hist,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
        )
        return jax.jit(fn)


def vote_features(
    local_hists: np.ndarray,
    lambda_l1: float,
    lambda_l2: float,
    min_data_in_leaf: float,
    min_sum_hessian: float,
    top_k: int,
) -> np.ndarray:
    """Voting-parallel feature pre-selection: each shard votes its top-k
    features by local best gain; returns the boolean union mask.  Only voted
    features' histograms then need the AllReduce — the comm-volume trade of
    LightGBM's `voting_parallel` tree learner."""
    s, f, b, _ = local_hists.shape
    mask = np.zeros(f, dtype=bool)
    for i in range(s):
        scores = np.asarray(
            _split_scores(jnp.asarray(local_hists[i]), lambda_l1, lambda_l2,
                          min_data_in_leaf, min_sum_hessian)
        )
        per_feature = scores.max(axis=1)
        k = min(top_k, f)
        top = np.argpartition(-per_feature, k - 1)[:k]
        mask[top[np.isfinite(per_feature[top])]] = True
    if not mask.any():
        mask[:] = True
    return mask
