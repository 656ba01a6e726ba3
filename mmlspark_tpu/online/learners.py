"""Online hashed linear learners with AllReduce weight averaging.

Reference: vw/VowpalWabbitBase.scala:71-556 (per-partition native VW fed
hashed examples; spanning-tree AllReduce between passes; TrainingStats ns
timers), vw/VowpalWabbitClassifier.scala, VowpalWabbitRegressor.scala,
VowpalWabbitBaseModel.scala.

TPU-native redesign: the weight table (2^bits) lives in HBM; one jitted
`lax.scan` runs the whole pass of per-example adaptive (AdaGrad) updates as
sparse scatter ops; the reference's spanning-tree AllReduce at end-of-pass
becomes a `jax.lax.pmean` over the mesh 'data' axis inside `shard_map` —
XLA compiles it to an ICI all-reduce.
"""
from __future__ import annotations

import time
from functools import partial
import jax
import jax.numpy as jnp
import numpy as np

from ..core.params import ComplexParam, Param, TypeConverters
from ..core.pipeline import Estimator, Model
from ..core.registry import register_stage
from ..core.schema import Table
from .featurizer import sparse_to_padded

__all__ = [
    "VowpalWabbitClassifier",
    "VowpalWabbitClassificationModel",
    "VowpalWabbitRegressor",
    "VowpalWabbitRegressionModel",
]


def _train_pass_impl(w, g2, idx, val, y, lr, l1, l2, loss: str):
    """One pass of per-example AdaGrad SGD over (n, A) padded sparse rows.

    Padded slots carry value 0 -> their gradient contribution is 0 and the
    scatter update is a no-op (featurizer.sparse_to_padded contract).
    """

    def step(carry, ex):
        w, g2 = carry
        i, v, yi = ex
        pred = jnp.sum(w[i] * v)
        if loss == "logistic":
            # y in {-1, +1}; d/dpred log(1 + exp(-y*pred))
            g = -yi * jax.nn.sigmoid(-yi * pred)
            ex_loss = jax.nn.softplus(-yi * pred)
        else:
            g = pred - yi
            ex_loss = 0.5 * (pred - yi) ** 2
        gi = g * v
        g2 = g2.at[i].add(gi * gi)
        denom = jnp.sqrt(g2[i]) + 1e-8
        wi = w[i]
        touched = (v != 0).astype(w.dtype)
        # everything additive so duplicate indices ACCUMULATE (featurizer
        # contract) and padded slots (touched=0) are exact no-ops; l1 is the
        # additive subgradient form of truncated gradient for the same reason
        delta = -lr * (gi / denom + l2 * wi * touched + l1 * jnp.sign(wi) * touched)
        w = w.at[i].add(delta)
        # all-zero rows are padding: no loss contribution, count 0
        valid = jnp.any(v != 0).astype(w.dtype)
        return (w, g2), (ex_loss * valid, valid)

    (w, g2), (losses, valids) = jax.lax.scan(step, (w, g2), (idx, val, y))
    return w, g2, jnp.sum(losses), jnp.sum(valids)


_train_pass = jax.jit(
    _train_pass_impl, static_argnames=("loss",), donate_argnums=(0, 1)
)


@partial(jax.jit, donate_argnums=())
def _predict_margin(w, idx, val):
    return jnp.sum(w[idx] * val, axis=-1)


def _allreduce_pass(mesh, loss: str):
    """Build the distributed pass: local scan per shard + end-of-pass pmean.

    Reference semantics: each VW node trains its partition independently,
    then the spanning-tree AllReduce averages models
    (VowpalWabbitBase.scala:434-462, endPass :363-368).
    """
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import shard_map

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data"), P("data"),
                  P(), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    def dist_pass(w, g2, idx, val, y, lr, l1, l2):
        w, g2, loss_sum, count = _train_pass_impl(
            w, g2, idx, val, y, lr, l1, l2, loss
        )
        w = jax.lax.pmean(w, "data")
        g2 = jax.lax.pmean(g2, "data")
        loss_sum = jax.lax.psum(loss_sum, "data")
        count = jax.lax.psum(count, "data")
        return w, g2, loss_sum, count

    return jax.jit(dist_pass, donate_argnums=(0, 1))


class _VowpalWabbitBase(Estimator):
    features_col = Param("sparse features column", default="features")
    label_col = Param("label column", default="label")
    prediction_col = Param("prediction column", default="prediction")
    num_bits = Param("weight-table bits (dim = 2^bits)", default=18,
                     converter=TypeConverters.to_int)
    num_passes = Param("passes over the data", default=1,
                       converter=TypeConverters.to_int)
    learning_rate = Param("base learning rate", default=0.5,
                          converter=TypeConverters.to_float)
    l1 = Param("l1 (truncated gradient)", default=0.0,
               converter=TypeConverters.to_float)
    l2 = Param("l2 decay", default=0.0, converter=TypeConverters.to_float)
    use_all_reduce = Param("shard the pass over the mesh 'data' axis with "
                           "end-of-pass weight averaging", default=False,
                           converter=TypeConverters.to_bool)
    initial_model = ComplexParam("warm-start weights (np array)", default=None)

    _loss = "squared"

    def _labels(self, table: Table) -> np.ndarray:
        raise NotImplementedError

    def _fit(self, table: Table) -> Model:
        t_ingest0 = time.perf_counter_ns()
        col = table[self.features_col]
        meta = table.get_meta(self.features_col)
        bits = int(meta.get("num_bits", self.num_bits))
        dim = 1 << bits
        idx, val = sparse_to_padded(col)
        y = self._labels(table)
        t_ingest = time.perf_counter_ns() - t_ingest0

        init = self.get_or_default("initial_model")
        w = jnp.asarray(init, jnp.float32) if init is not None else jnp.zeros(
            (dim,), jnp.float32
        )
        g2 = jnp.zeros((dim,), jnp.float32)
        lr = jnp.float32(self.learning_rate)
        l1 = jnp.float32(self.l1)
        l2 = jnp.float32(self.l2)

        mesh = None
        if self.use_all_reduce:
            from ..parallel.mesh import default_mesh

            mesh = default_mesh()
            nd = mesh.shape.get("data", 1)
            # zero-pad to a multiple of the data axis: all-zero values make
            # the padded rows exact no-ops in the update and the loss count
            rem = (-len(idx)) % nd
            if rem:
                idx = np.concatenate([idx, np.zeros((rem, idx.shape[1]), idx.dtype)])
                val = np.concatenate([val, np.zeros((rem, val.shape[1]), val.dtype)])
                y = np.concatenate([y, np.zeros((rem,), y.dtype)])
            pass_fn = _allreduce_pass(mesh, self._loss)
        else:
            pass_fn = partial(_train_pass, loss=self._loss)

        t_learn0 = time.perf_counter_ns()
        yj = jnp.asarray(y)
        ij = jnp.asarray(idx)
        vj = jnp.asarray(val)
        n_passes = int(self.num_passes)
        if n_passes > 1:
            # all passes ride ONE dispatch (a scan over the jitted pass):
            # VW's multipass re-reads its cache file per pass; here the
            # only per-pass cost was a host sync for the loss, and even
            # that gates the loop
            def scanned(w, g2):
                def body(carry, _):
                    w, g2 = carry
                    w, g2, ls, ct = pass_fn(w, g2, ij, vj, yj, lr, l1, l2)
                    return (w, g2), (ls, ct)
                return jax.lax.scan(body, (w, g2), None, length=n_passes)

            (w, g2), (loss_sums, counts) = jax.jit(scanned)(w, g2)
            losses = [float(ls) / max(float(ct), 1.0)
                      for ls, ct in zip(loss_sums, counts)]
        else:
            w, g2, loss_sum, count = pass_fn(w, g2, ij, vj, yj, lr, l1, l2)
            losses = [float(loss_sum) / max(float(count), 1.0)]
        t_learn = time.perf_counter_ns() - t_learn0

        stats = Table({
            "pass": np.arange(len(losses)),
            "average_loss": np.asarray(losses, np.float64),
            "ingest_time_ns": np.full(len(losses), t_ingest, np.int64),
            "learn_time_ns": np.full(len(losses), t_learn, np.int64),
            "num_examples": np.full(len(losses), len(table) , np.int64),
            "num_shards": np.full(
                len(losses),
                mesh.shape.get("data", 1) if mesh is not None else 1,
                np.int64,
            ),
        })
        return self._make_model(np.asarray(w), stats)

    def _make_model(self, weights: np.ndarray, stats: Table) -> Model:
        raise NotImplementedError


class _VowpalWabbitModelBase(Model):
    features_col = Param("sparse features column", default="features")
    prediction_col = Param("prediction column", default="prediction")
    weights = ComplexParam("weight table (np array)")
    performance_statistics = ComplexParam("per-pass TrainingStats table",
                                          default=None)

    def _margins(self, table: Table) -> np.ndarray:
        idx, val = sparse_to_padded(table[self.features_col])
        if len(idx) == 0:
            return np.zeros((0,), np.float32)
        w = jnp.asarray(self.weights, jnp.float32)
        return np.asarray(_predict_margin(w, jnp.asarray(idx), jnp.asarray(val)))


@register_stage
class VowpalWabbitRegressor(_VowpalWabbitBase):
    """Online squared-loss regressor (reference VowpalWabbitRegressor.scala)."""

    _loss = "squared"

    def _labels(self, table: Table) -> np.ndarray:
        return np.asarray(table[self.label_col], np.float32)

    def _make_model(self, weights, stats):
        return VowpalWabbitRegressionModel(
            weights=weights, performance_statistics=stats,
            features_col=self.features_col, prediction_col=self.prediction_col,
        )


@register_stage
class VowpalWabbitRegressionModel(_VowpalWabbitModelBase):
    def _transform(self, table: Table) -> Table:
        return table.with_column(self.prediction_col, self._margins(table))


@register_stage
class VowpalWabbitClassifier(_VowpalWabbitBase):
    """Online logistic classifier; labels {0,1} mapped to {-1,+1}
    (reference VowpalWabbitClassifier.scala:116)."""

    probability_col = Param("probability column", default="probability")
    _loss = "logistic"

    def _labels(self, table: Table) -> np.ndarray:
        y = np.asarray(table[self.label_col], np.float32)
        return np.where(y > 0, 1.0, -1.0).astype(np.float32)

    def _make_model(self, weights, stats):
        return VowpalWabbitClassificationModel(
            weights=weights, performance_statistics=stats,
            features_col=self.features_col, prediction_col=self.prediction_col,
            probability_col=self.probability_col,
        )


@register_stage
class VowpalWabbitClassificationModel(_VowpalWabbitModelBase):
    probability_col = Param("probability column", default="probability")

    def _transform(self, table: Table) -> Table:
        margin = self._margins(table)
        prob = 1.0 / (1.0 + np.exp(-margin))
        out = table.with_column(self.probability_col, prob.astype(np.float32))
        return out.with_column(
            self.prediction_col, (prob > 0.5).astype(np.int64)
        )
