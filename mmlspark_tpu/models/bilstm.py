"""BiLSTM sequence tagger with bucketed padding under jit.

Reference capability: the "Medical Entity Extraction" BiLSTM notebook served
through CNTK dynamic axes (SURVEY §5 long-context note: the BiLSTM
needs dynamic-shape padding/bucketing on XLA instead).
XLA has no dynamic axes, so variable-length token sequences are padded to a
small set of bucket lengths — one compiled program per bucket — with masked
loss/metrics.  `lax.scan` inside flax's nn.RNN keeps the recurrence
compiler-friendly.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from ..core.params import ComplexParam, Param, TypeConverters
from ..core.pipeline import Estimator, Model
from ..core.registry import register_stage
from ..core.schema import Table

__all__ = ["BiLSTMTagger", "SequenceTagger", "SequenceTaggerModel",
           "bucket_length", "pad_to_buckets"]

DEFAULT_BUCKETS = (16, 32, 64, 128, 256)


def bucket_length(n: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    """Smallest bucket >= n; sequences beyond the last bucket get an exact
    bucket of their own length (an extra compile, never silent truncation)."""
    for b in buckets:
        if n <= b:
            return b
    return n


def pad_to_buckets(seqs: List[np.ndarray],
                   buckets: Sequence[int] = DEFAULT_BUCKETS,
                   pad_value: int = 0):
    """Group sequences by bucket: {bucket: (ids (B,L), lengths (B,), rows)}.

    One jit compile per bucket instead of per distinct length.
    """
    groups: Dict[int, List[int]] = {}
    for i, s in enumerate(seqs):
        groups.setdefault(bucket_length(len(s), buckets), []).append(i)
    out = {}
    for b, rows in groups.items():
        ids = np.full((len(rows), b), pad_value, np.int32)
        lens = np.zeros(len(rows), np.int32)
        for j, r in enumerate(rows):
            s = np.asarray(seqs[r][:b], np.int32)
            ids[j, : len(s)] = s
            lens[j] = len(s)
        out[b] = (ids, lens, np.asarray(rows))
    return out


class BiLSTMTagger(nn.Module):
    """Embedding -> BiLSTM -> per-token tag logits."""

    vocab_size: int
    num_tags: int
    embed_dim: int = 64
    hidden: int = 128

    @nn.compact
    def __call__(self, token_ids, lengths):
        x = nn.Embed(self.vocab_size, self.embed_dim)(token_ids)
        fwd = nn.RNN(nn.OptimizedLSTMCell(self.hidden))(
            x, seq_lengths=lengths
        )
        bwd = nn.RNN(nn.OptimizedLSTMCell(self.hidden), reverse=True,
                     keep_order=True)(x, seq_lengths=lengths)
        h = jnp.concatenate([fwd, bwd], axis=-1)
        return nn.Dense(self.num_tags)(h)


def _loss_fn(params, apply_fn, ids, lens, tags):
    logits = apply_fn({"params": params}, ids, lens)
    mask = (jnp.arange(ids.shape[1])[None, :] < lens[:, None]).astype(
        jnp.float32
    )
    ll = optax.softmax_cross_entropy_with_integer_labels(logits, tags)
    return jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@register_stage
class SequenceTagger(Estimator):
    """Token-level tagger: fit on (tokens, tags) list columns.

    Vocabulary is built from the training tokens; OOV -> index 1, pad -> 0.
    """

    tokens_col = Param("column of token lists", default="tokens")
    tags_col = Param("column of tag lists", default="tags")
    prediction_col = Param("predicted tag list column", default="prediction")
    embed_dim = Param("embedding dim", default=64,
                      converter=TypeConverters.to_int)
    hidden = Param("LSTM hidden size", default=128,
                   converter=TypeConverters.to_int)
    epochs = Param("training epochs", default=10,
                   converter=TypeConverters.to_int)
    learning_rate = Param("adam lr", default=1e-3,
                          converter=TypeConverters.to_float)
    buckets = Param("padding buckets", default=list(DEFAULT_BUCKETS),
                    converter=TypeConverters.to_list_int)
    seed = Param("init seed", default=0, converter=TypeConverters.to_int)

    def _fit(self, table: Table) -> "SequenceTaggerModel":
        if len(table) == 0:
            raise ValueError("SequenceTagger.fit: no training rows")
        token_lists = [list(map(str, t)) for t in table[self.tokens_col]]
        tag_lists = [list(map(str, t)) for t in table[self.tags_col]]
        for i, (toks, tags) in enumerate(zip(token_lists, tag_lists)):
            if len(toks) != len(tags):
                raise ValueError(
                    f"row {i}: {len(toks)} tokens but {len(tags)} tags — "
                    "token/tag lists must align"
                )
        vocab = {"<pad>": 0, "<unk>": 1}
        for toks in token_lists:
            for t in toks:
                vocab.setdefault(t, len(vocab))
        tag_vocab: Dict[str, int] = {}
        for tags in tag_lists:
            for t in tags:
                tag_vocab.setdefault(t, len(tag_vocab))

        id_seqs = [
            np.array([vocab.get(t, 1) for t in toks], np.int32)
            for toks in token_lists
        ]
        tag_seqs = [
            np.array([tag_vocab[t] for t in tags], np.int32)
            for tags in tag_lists
        ]
        buckets = tuple(self.buckets)
        module = BiLSTMTagger(
            vocab_size=len(vocab), num_tags=len(tag_vocab),
            embed_dim=int(self.embed_dim), hidden=int(self.hidden),
        )
        rng = jax.random.PRNGKey(int(self.seed))
        first_b = bucket_length(len(id_seqs[0]), buckets)
        params = module.init(
            rng, jnp.zeros((1, first_b), jnp.int32), jnp.ones((1,), jnp.int32)
        )["params"]
        opt = optax.adam(float(self.learning_rate))
        opt_state = opt.init(params)

        @partial(jax.jit, static_argnames=())
        def train_step(params, opt_state, ids, lens, tags):
            loss, grads = jax.value_and_grad(_loss_fn)(
                params, module.apply, ids, lens, tags
            )
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        bucketed_ids = pad_to_buckets(id_seqs, buckets)
        bucketed_tags = {
            b: pad_to_buckets([tag_seqs[r] for r in rows], (b,))[b][0]
            for b, (_, _, rows) in bucketed_ids.items()
        }
        # no per-step host sync: losses stay on device so dispatch pipelines
        for _ in range(int(self.epochs)):
            for b, (ids, lens, rows) in bucketed_ids.items():
                params, opt_state, _loss = train_step(
                    params, opt_state, jnp.asarray(ids), jnp.asarray(lens),
                    jnp.asarray(bucketed_tags[b]),
                )
        return SequenceTaggerModel(
            model_params=jax.device_get(params),
            vocab=vocab, tag_vocab=tag_vocab,
            module_config={
                "vocab_size": len(vocab), "num_tags": len(tag_vocab),
                "embed_dim": int(self.embed_dim), "hidden": int(self.hidden),
            },
            tokens_col=self.tokens_col, prediction_col=self.prediction_col,
            buckets=list(buckets),
        )


@register_stage
class SequenceTaggerModel(Model):
    tokens_col = Param("column of token lists", default="tokens")
    prediction_col = Param("predicted tag list column", default="prediction")
    buckets = Param("padding buckets", default=list(DEFAULT_BUCKETS),
                    converter=TypeConverters.to_list_int)
    model_params = ComplexParam("flax params pytree")
    vocab = ComplexParam("token vocabulary")
    tag_vocab = ComplexParam("tag vocabulary")
    module_config = ComplexParam("BiLSTMTagger config")

    def _module(self) -> BiLSTMTagger:
        return BiLSTMTagger(**self.module_config)

    def _transform(self, table: Table) -> Table:
        module = self._module()
        vocab = self.vocab
        inv_tags = {v: k for k, v in self.tag_vocab.items()}
        token_lists = [list(map(str, t)) for t in table[self.tokens_col]]
        id_seqs = [
            np.array([vocab.get(t, 1) for t in toks], np.int32)
            for toks in token_lists
        ]
        out = np.empty(len(table), dtype=object)
        if not id_seqs:
            return table.with_column(self.prediction_col, out)

        # jit once per model instance (params passed as an argument), so
        # repeated transform() calls reuse the per-bucket compile cache
        if not hasattr(self, "_jit_predict"):
            @jax.jit
            def predict(params, ids, lens):
                logits = module.apply({"params": params}, ids, lens)
                return jnp.argmax(logits, axis=-1)

            self._jit_predict = predict

        for b, (ids, lens, rows) in pad_to_buckets(
            id_seqs, tuple(self.buckets)
        ).items():
            preds = np.asarray(self._jit_predict(
                self.model_params, jnp.asarray(ids), jnp.asarray(lens)
            ))
            for j, r in enumerate(rows):
                n = int(lens[j])
                out[r] = [inv_tags[int(p)] for p in preds[j, :n]]
        return table.with_column(self.prediction_col, out)
