"""GlmMoeLM: a decoder-only LM of the GLM-4.7-Flash family
(zai-org/GLM-4.7-Flash, `model_type` `glm4_moe_lite`) ON THE TRAINING PATH:
latent attention (MLA) through the flash kernels that carry a backward,
a leading dense layer and then routed layers with sigmoid scores, a
selection bias that a controller moves instead of an auxiliary loss, a
shared expert, and a multi-token-prediction (MTP) module in the loss.

    block:  x += MLA(rms(x));  x += F(rms(x))
            F the dense gated MLP in the first `dense_layers`, after them
            s = sigmoid(Wr y) f32;  T = top_k of s + b;  w = s[T] / (sum
            s[T] + 1e-20) * scaling;  sum_{e in T, held} w_e E_e(y) +
            Shared(y)
    MLA:    `longcat_lm._LatentAttention` without its `mla_scale_*` gains,
            scores over nope + rope (= v here) scaled 1 / sqrt(nope + rope)
    MTP, depth 1 (DeepSeek-V3, arXiv:2412.19437, 2.2), h the last layer's
            output BEFORE the final norm:
            h'_i = M [rms_e(Emb(t_{i+1})) ; rms_h(h_i)];  one sparse block
            of its own over h';  logits Head(rms(.)) with the main
            model's embedding and head;  position i predicts t_{i+2}
    loss = CE_main + mtp_loss_weight * CE_mtp, each a mean over its own
            targets (S - 1 and S - 2 a sequence)
    after every optimizer step, in every routed layer:
            b_e += bias_update_rate * sign(mean load - load_e)
            over the step's counts of ALL experts (arXiv:2408.15664)

One piece of code with the served MoE models: the routed layer is
`moe_lm._SparseMLP` (`scores="sigmoid"`, the bias in the `controller`
collection where no optimizer reaches it), attention `_LatentAttention`
(`train=True`: `fused_attention`), norms and dense MLPs that module's.
Weights are kept in `param_dtype` (float32) and read in `dtype`
(bfloat16); every block is a `jax.checkpoint`.

THE SHARE, as in `MoELM`: `experts_held = (lo, hi)` of `num_experts` in
every routed layer and in the MTP block; `vocab_size` the slice held.

TRAINING goes through `models.training.make_lm_train_epoch`, which finds
`lm_objective` (the loss above, its parts and the step's routing
statistics; both heads' cross-entropies in chunks of `loss_chunk` tokens,
so that no [tokens, vocab] float32 array is whole) and `lm_controller`
(the bias rule) here.  What it is handed as `params` is the model's
VARIABLES, `{"params": ..., "controller": ...}`; the optimizer sees
`params` alone.  The MTP module computes all S positions (the last one
merges a token that does not exist, is nobody's in the load counts and has
no target; causality keeps it from the others).  Serving (`prefill`,
`decode_step`, a self-drafting decode step for the MTP module) is not
built.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .longcat_lm import _LatentAttention
from .moe_lm import _DenseMLP, _normal, _RMSNorm, _SparseMLP

__all__ = ["GlmMoeLM", "TRAIN_COUNTERS"]

BIAS = "controller"     # the collection the selection biases live in
# (a part of `lm_objective`'s, the counter it feeds): what
# `training.record_lm_stats` reads
TRAIN_COUNTERS = (
    ("moe_assignments", "training.moe.assignments"),
    ("moe_experts_touched", "training.moe.experts_touched"),
    ("moe_load_max", "training.moe.load_max"),
    ("moe_load_max_all", "training.moe.load_max_all"),
    ("attn_pairs", "training.attn.pairs"),
    ("mtp_tokens", "training.mtp.tokens"),
)


class _Block(nn.Module):
    """One layer: latent attention, then the dense MLP (`sparse` None) or
    the routed layer."""

    attn: dict
    sparse: Optional[dict]
    dense_width: int
    eps: float
    dtype: Any
    param_dtype: Any
    train: bool = False

    @nn.compact
    def __call__(self, x, live=None):
        dt, kept = self.dtype, self.param_dtype

        def norm(name):
            return _RMSNorm(self.eps, dt, 1.0, kept, name=name)

        a, _rows = _LatentAttention(
            dtype=dt, eps=self.eps, lora_scales=False, param_dtype=kept,
            name="attn", **self.attn)(norm("attn_norm")(x), train=self.train)
        x = x + a.astype(dt)
        y = norm("mlp_norm")(x)
        if self.sparse is None:
            with jax.named_scope("mlp.dense"):
                m = _DenseMLP(self.dense_width, dt, kept, name="mlp")(y)
        else:
            m = _SparseMLP(dtype=dt, param_dtype=kept, name="moe",
                           **self.sparse)(y, live, self.train)
        return x + m.astype(dt)


_RematBlock = nn.remat(_Block)


class _MTP(nn.Module):
    """The multi-token-prediction module: the merge of the next token's
    embedding with the trunk's hidden state, one sparse block, a norm of
    its own before the shared head."""

    block: Any          # the block's class, and its arguments
    block_args: tuple
    eps: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, h, emb_next, live):
        dt, kept = self.dtype, self.param_dtype
        e = h.shape[-1]

        def norm(name):
            return _RMSNorm(self.eps, dt, 1.0, kept, name=name)

        with jax.named_scope("mtp.merge"):
            m = self.param("eh_proj", _normal((2 * e) ** -0.5), (2 * e, e),
                           kept).astype(dt)
            x = jnp.dot(jnp.concatenate(
                [norm("enorm")(emb_next), norm("hnorm")(h)], -1), m)
        with jax.named_scope("mtp.block"):
            x = self.block(*self.block_args, name="block")(x, live)
        return norm("final_norm")(x)


def _chunked_ce(x, head, targets, mask, chunk: int, dtype):
    """sum over the rows of mask * (logsumexp(x head) - (x head)[target]),
    float32, `chunk` rows at a time: x [T, E] normed, head [E, V] as
    KEPT (read in `dtype` inside a chunk, so that its gradient sums over
    the chunks in the kept type), targets [T] int32, mask [T] float32.
    A chunk's logits are made again in the backward, never kept."""
    t = x.shape[0]
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        x, targets, mask = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (x, targets, mask))

    @jax.checkpoint
    def one(head, xc, tc, mc):
        logits = jnp.dot(xc, head.astype(dtype),
                         preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, -1) - picked) * mc)

    def body(total, rows):
        return total + one(head, *rows), None

    rows = tuple(a.reshape(-1, chunk, *a.shape[1:])
                 for a in (x, targets, mask))
    return jax.lax.scan(body, jnp.zeros((), jnp.float32), rows)[0]


class GlmMoeLM(nn.Module):
    """Decoder-only LM over int32 token ids [B, S]; defaults are a tiny
    preset, the published sizes come from a configuration file
    (benchmarks/configs/glm-4.7-flash.json through `from_config`)."""

    vocab_size: int = 128
    embed_dim: int = 64
    num_layers: int = 3
    dense_layers: int = 1           # leading layers with a dense MLP
    num_heads: int = 4
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 24
    q_lora_rank: int = 32
    kv_lora_rank: int = 24
    dense_width: int = 128
    expert_width: int = 32
    shared_width: int = 32
    num_experts: int = 8            # published count
    experts_held: Tuple[int, int] = (0, 8)
    top_k: int = 2
    routed_scaling: float = 1.8
    rope_theta: float = 1e6
    eps: float = 1e-5
    mtp_layers: int = 1             # 0: no multi-token-prediction module
    mtp_loss_weight: float = 0.3
    bias_update_rate: float = 1e-3
    loss_chunk: int = 2048
    max_len: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    train_counters = TRAIN_COUNTERS

    @classmethod
    def from_config(cls, cfg: dict, max_len: int, dtype=jnp.bfloat16,
                    **kw):
        """The model of a `glm4_moe_lite` `config.json` cut as its file
        says: `num_hidden_layers` leading layers, `n_routed_experts` held
        of `published.n_routed_experts` (the first ones), `vocab_size`
        rows."""
        if cfg["num_nextn_predict_layers"] > 1:
            raise NotImplementedError("one MTP module (depth 1) is built")
        return cls(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            dense_layers=cfg["first_k_dense_replace"],
            num_heads=cfg["num_attention_heads"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            shared_width=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            num_experts=cfg["published"]["n_routed_experts"],
            experts_held=(0, cfg["n_routed_experts"]),
            top_k=cfg["num_experts_per_tok"],
            routed_scaling=float(cfg["routed_scaling_factor"]),
            rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
            mtp_layers=cfg["num_nextn_predict_layers"],
            max_len=max_len, dtype=dtype, **kw)

    # ---- the network -----------------------------------------------------
    def _block_args(self, sparse: bool, train: bool):
        """(the block's class, its arguments)."""
        attn = dict(heads=self.num_heads, nope=self.qk_nope_dim,
                    rope=self.qk_rope_dim, v_dim=self.v_head_dim,
                    q_rank=self.q_lora_rank, kv_rank=self.kv_lora_rank,
                    theta=self.rope_theta)
        routed = dict(num_experts=self.num_experts, top_k=self.top_k,
                      width=self.expert_width, shared_width=self.shared_width,
                      scaling=self.routed_scaling,
                      held=tuple(self.experts_held), renormalise=True,
                      choice_bias=True, scores="sigmoid",
                      bias_collection=BIAS)
        return (_RematBlock if self.remat and train else _Block,
                (attn, routed if sparse else None, self.dense_width,
                 self.eps, self.dtype, self.param_dtype, train))

    def _table(self):
        return self.param("embed", _normal(1.0),
                          (self.vocab_size, self.embed_dim), self.param_dtype)

    def _head_weight(self):
        """As kept: whoever multiplies by it reads it in `dtype`."""
        return self.param("head", _normal(self.embed_dim ** -0.5),
                          (self.embed_dim, self.vocab_size), self.param_dtype)

    def _norm(self, name):
        return _RMSNorm(self.eps, self.dtype, 1.0, self.param_dtype,
                        name=name)

    def _trunk(self, tokens, train: bool):
        """-> (table, embedded tokens, the last layer's output before the
        final norm)."""
        table = self._table()
        x = emb = table[tokens].astype(self.dtype)
        for i in range(self.num_layers):
            block, args = self._block_args(i >= self.dense_layers, train)
            x = block(*args, name=f"layer{i}")(x)
        return table, emb, x

    def _mtp(self, table, h, tokens, train: bool):
        """-> the MTP module's normed output [B, S, E]: position i has
        merged t_{i+1} (the last position a token that does not exist)."""
        s = tokens.shape[1]
        emb_next = table[jnp.roll(tokens, -1, 1)].astype(self.dtype)
        live = jnp.broadcast_to(jnp.arange(s) < s - 1, tokens.shape)
        return _MTP(*self._block_args(True, train), self.eps, self.dtype,
                    self.param_dtype, name="mtp")(h, emb_next, live)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        """-> (logits [B, S, V] float32, taps); `mtp_logits` among the
        taps where the model has the module ([B, S, V]: position i's are
        of t_{i+2}, the last position's of nothing).  Whole logits: for
        small sizes; training takes `losses`."""
        table, emb, h = self._trunk(tokens, train)
        head = self._head_weight().astype(self.dtype)
        taps = {"embed": emb, "hidden": h}
        logits = jnp.dot(self._norm("final_norm")(h), head,
                         preferred_element_type=jnp.float32)
        taps["logits"] = logits
        if self.mtp_layers:
            taps["mtp_logits"] = jnp.dot(
                self._mtp(table, h, tokens, train), head,
                preferred_element_type=jnp.float32)
        return logits, taps

    @nn.compact
    def losses(self, tokens):
        """-> {"ce_main", "ce_mtp"}: the two heads' mean cross-entropies
        (ce_mtp 0 without the module), the training path."""
        b, s = tokens.shape
        table, _emb, h = self._trunk(tokens, True)
        head = self._head_weight()

        def ce(x, shift: int):
            """Position i's row against t_{i + shift}."""
            targets = jnp.roll(tokens, -shift, 1).reshape(-1)
            mask = jnp.broadcast_to(jnp.arange(s) < s - shift, (b, s))
            total = _chunked_ce(x.reshape(b * s, -1), head, targets,
                                mask.reshape(-1).astype(jnp.float32),
                                self.loss_chunk, self.dtype)
            return total / (b * (s - shift))

        with jax.named_scope("loss.main"):
            out = {"ce_main": ce(self._norm("final_norm")(h), 1)}
        out["ce_mtp"] = jnp.zeros((), jnp.float32)
        if self.mtp_layers:
            x = self._mtp(table, h, tokens, True)
            with jax.named_scope("loss.mtp"), jax.named_scope("mtp.head"):
                out["ce_mtp"] = ce(x, 2)
        return out

    # ---- what `make_lm_train_epoch` finds ----------------------------------
    def lm_objective(self, variables, tokens):
        """-> (loss, parts).  `variables`: {"params", "controller"}.
        parts: `ce_main`, `ce_mtp` (float32), the step's routing
        statistics summed over the routed layers and its work counts
        (int32; `TRAIN_COUNTERS` names them), and `load`, per routed
        layer the assignments on each of all the experts: the
        controller's reading, which `lm_controller` takes and no one is
        handed back."""
        b, s = tokens.shape
        # the two collections alone: what `init` left in `stats` or
        # `load` would be summed into this step's
        out, mut = self.apply({k: variables[k] for k in ("params", BIAS)},
                              tokens, method=self.losses,
                              mutable=["stats", "load"])
        loss = out["ce_main"] + self.mtp_loss_weight * out["ce_mtp"]
        stats: dict = {}
        for path, value in jax.tree_util.tree_leaves_with_path(mut["stats"]):
            name = path[-1].key
            stats[name] = stats.get(name, 0) + value
        load = mut["load"]
        attentions = self.num_layers * s * (s + 1) // 2
        if self.mtp_layers:
            attentions += (s - 1) * s // 2
        parts = dict(
            out, load=load,
            moe_assignments=stats["moe_live_assignments"],
            moe_experts_touched=stats["moe_experts_touched"],
            moe_load_max=stats["moe_load_max"],
            moe_load_max_all=sum(jnp.max(c) for c in jax.tree.leaves(load)),
            attn_pairs=jnp.asarray(b * attentions, jnp.int32),
            mtp_tokens=jnp.asarray(b * (s - 2) * bool(self.mtp_layers),
                                   jnp.int32))
        return loss, parts

    def lm_controller(self, variables, parts):
        """The variables after the step's bias rule: every routed layer's
        bias moves by `bias_update_rate` towards its mean load."""
        with jax.named_scope("moe.bias_update"):
            def move(bias, counts):
                counts = counts.astype(jnp.float32)
                return bias + self.bias_update_rate * jnp.sign(
                    jnp.mean(counts) - counts)

            def walk(biases, loads):
                if "bias" in biases:
                    return {"bias": move(biases["bias"], loads["counts"])}
                return {k: walk(v, loads[k]) for k, v in biases.items()}

            return {**variables, BIAS: walk(variables[BIAS], parts["load"])}
