"""LongCatLM: a decoder-only LM of the LongCat-Flash family
(meituan-longcat/LongCat-Flash-Chat): latent attention (MLA) over ONE
cached row a position, and shortcut-connected blocks of two attentions,
two dense MLPs and one routed layer whose experts include zero-compute
(identity) ones.

One block, x [.., E] (RMSNorm pre-norm throughout):

    x = x + MLA_0(rms(x));  y0 = rms(x)
    s = MoE(y0)                      # the shortcut: read here ...
    x = x + MLP_0(y0)
    x = x + MLA_1(rms(x));  x = x + MLP_1(rms(x))
    x = x + s                        # ... added at the block's end

    MLA(y):  q = Wqb rms(Wqa y) -> [H, nope + rope];  (c, kr) = Wkva y
             c = rms(c);  q *= sqrt(E / q_rank);  c *= sqrt(E / kv_rank)
             q_rope, kr = rope(q_rope), rope(kr)   (kr: one for all heads)
             (k_nope, v) = Wkvb c -> [H, nope], [H, v]
             scores = (q_nope . k_nope + q_rope . kr) / sqrt(nope + rope)
    CACHED a position: the row (c, kr), kv_rank + rope values.

    MoE(y):  p = softmax(Wr y) over the FFN experts and then the identity
             experts; top-k of p + b (b: a selection bias, for the choice
             only); w = scaling * p[top], not renormalised;
             sum_k w_k (expert_k(y) if an FFN expert else y)

The routed layer is `moe_lm._SparseMLP` (one piece of code for both MoE
models), norms and dense MLPs are that module's too.  The serving
contract is `MoELM`'s (`__call__`, `prefill`, `decode_step`,
`cache_kinds`, `layer_kinds`, `cache_rows`, `stat_counters`), so
`ContinuousBatcher(model, variables, paged=True)` serves it: one cache
kind `("latent", None)` whose sublayers keep ONE pool of rows (`kv_rank +
rope` values in whole lane tiles, `latent_row_width`), and TWO cached
sublayers a block.

The admission computes attention EXPANDED, as written (`mla.expand`: K
and V of every head made from the latent rows, the flash forward at q/k
heads of nope + rope and v heads of v).  A decode step computes it
ABSORBED, in the latent space (`mla.absorb`): q' = q_nope . Wkvb_K
[H, kv_rank] kept in float32, scores = q' . c + q_rope . kr against the
cached rows, o' = softmax . c, out_h = o'_h . Wkvb_V
(ops/paged_attention.py `paged_mla_attention`).

THE SHARE.  `experts_held = (lo, hi)` of the `num_experts` FFN experts,
as in `MoELM`; the identity experts hold no weights and are computed for
every token on every chip.  `vocab_size` is the slice held.

A verifier that asks for the `routing` collection gets, besides the
routed layer's taps, from every latent attention of a DECODE step the
query as the page walk multiplies it (`mla_query`, float32) and the
q_nope it was absorbed from (`mla_q_nope`), both of the first
`TAP_HEADS` heads: the absorbed product judged by itself.
"""
from __future__ import annotations

import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .moe_lm import (STAT_NAMES, ZERO_STAT, _DenseMLP, _normal, _RMSNorm,
                     _SparseMLP, counters_of)
from .transformer import _rope, _single_tpu, default_attn

__all__ = ["LongCatLM", "TAP_HEADS"]

TAP_HEADS = 8       # heads whose absorbed query a verifier is handed
# an admission's tokens go through the routed experts this many at a
# time: the dropless dispatch buffers are sized for every assignment of a
# call, of which a 32-way share's experts draw a forty-eighth
MOE_TOKEN_CHUNK = 1024


def latent_row_width(kv_rank: int, rope: int) -> int:
    """Width of one cached row: latent and rope key, up to whole lane
    tiles."""
    return -(-(kv_rank + rope) // 128) * 128


def _kv_gain(embed_dim: int, kv_rank: int) -> float:
    """`mla_scale_kv_lora`: what the normed latent is multiplied by."""
    return math.sqrt(embed_dim / kv_rank)


class _LatentAttention(nn.Module):
    """Latent attention (MLA).  `lora_scales`: LongCat's `mla_scale_*`
    gains on the two norms (a family without them gives False).
    `param_dtype`: what the weights are kept in (None: `dtype`); they are
    read in `dtype`."""

    heads: int
    nope: int
    rope: int
    v_dim: int
    q_rank: int
    kv_rank: int
    theta: float
    eps: float
    dtype: Any
    lora_scales: bool = True
    param_dtype: Any = None

    @nn.compact
    def __call__(self, y, cache=None, pos=None, page_table=None,
                 train: bool = False, lengths=None):
        """cache None: causal attention over y [B, S, E]; returns (out
        [B, S, E], (rows [B, S, row = `latent_row_width`],)); `train`: by
        the attention that carries a backward (`_expanded`); `lengths`
        [B]: a row's own positions (an admission's bucket is padded past
        them; the rest attend nothing and come back zero).  Otherwise y
        is [B, 1, E] at per-slot `pos` [B] and cache this sublayer's pool
        ([NP, page, row],) under `page_table` [B, MP]; returns
        (out, (pool,))."""
        b, s, e = y.shape
        h, dn, dr, dv = self.heads, self.nope, self.rope, self.v_dim
        rq, rk, dt = self.q_rank, self.kv_rank, self.dtype
        decode = cache is not None

        def proj(name, n_in, n_out):
            return self.param(name, _normal(n_in ** -0.5), (n_in, n_out),
                              self.param_dtype or dt).astype(dt)

        def norm(name, gain):
            return _RMSNorm(self.eps, dt, gain if self.lora_scales else 1.0,
                            self.param_dtype, name=name)

        # mla_scale_q_lora rides the query norm (it commutes with Wqb)
        q_lat = norm("q_norm", math.sqrt(e / rq))(
            jnp.dot(y, proj("wqa", e, rq)))
        q = jnp.dot(q_lat, proj("wqb", rq, h * (dn + dr))).reshape(
            b, s, h, dn + dr)
        ckr = jnp.dot(y, proj("wkva", e, rk + dr))
        c = norm("kv_norm", _kv_gain(e, rk))(ckr[..., :rk])
        positions = (jnp.arange(s) if not decode
                     else pos[:, None] + jnp.arange(s)[None])
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], positions,
                                            self.theta)
        kr = _rope(ckr[..., None, rk:], positions, self.theta)[:, :, 0]
        # what is cached: the latent, the rope key, zeros up to whole
        # 128-lane tiles (the chip's tiled HBM layout pads a narrower
        # row to the same bytes, and a DMA cannot slice inside a tile)
        rows = jnp.concatenate(
            [c, kr, jnp.zeros((b, s, latent_row_width(rk, dr) - rk - dr),
                              dt)], -1)
        wkvb = proj("wkvb", rk, h * (dn + dv)).reshape(rk, h, dn + dv)
        with jax.named_scope("attn.latent"):
            if decode:
                with jax.named_scope("mla.absorb"):
                    a, cache = self._absorbed(q_nope, q_rope, rows, wkvb,
                                              cache, pos, page_table)
            else:
                with jax.named_scope("mla.expand"):
                    a = self._expanded(q_nope, q_rope, c, kr, wkvb, train,
                                       lengths)
                cache = (rows,)
        a = a.astype(dt).reshape(b, s, h * dv)
        return jnp.dot(a, proj("wo", h * dv, e)), cache

    def _expanded(self, q_nope, q_rope, c, kr, wkvb, train: bool = False,
                  lengths=None):
        """K and V of every head from the latent rows, then the causal
        flash forward at q/k heads of nope + rope and v heads of v, over
        each row's first `lengths` positions (None: all).
        `train`: through `fused_attention`, the kernels that carry a
        backward (`default_attn`: on one TPU, the XLA composition
        elsewhere), which take q, k and v of ONE width: a v narrower than
        q/k (LongCat's 128 under 192) is padded with zero columns up to
        it and the output cut back."""
        b, s, h, dn = q_nope.shape
        kv = jnp.einsum("bsr,rhd->bshd", c, wkvb)
        k = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(kr[:, :, None], (b, s, h, self.rope))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        v = kv[..., dn:]
        if not train:
            from ..ops.attention_kernels import prefill_attention

            return prefill_attention(q, k, v, None, lengths,
                                     kernel=_single_tpu())
        if self.v_dim > q.shape[-1]:
            raise NotImplementedError(
                "training attends at one head width, q/k's: a v wider "
                f"than it ({self.v_dim} > {q.shape[-1]}) is not built")
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - self.v_dim),))
        return default_attn(True)(q, k, v)[..., :self.v_dim]

    def _absorbed(self, q_nope, q_rope, rows, wkvb, cache, pos, page_table):
        """Write this token's row into its page, then attend in the
        latent space: the page walk on one TPU, the gather composition
        elsewhere."""
        from ..ops.paged_attention import paged_mla_attention

        b, s, h, dn = q_nope.shape
        if s != 1:
            raise NotImplementedError(
                "LongCatLM decodes one token a slot over its latent pools; "
                "block decode (speculation, shared prefixes) is not built "
                "for a one-pool cache kind")
        (pool,) = cache
        page, mp = pool.shape[1], page_table.shape[1]
        pg = page_table[jnp.arange(b), jnp.minimum(pos // page, mp - 1)]
        pool = pool.at[pg, pos % page].set(rows[:, 0].astype(pool.dtype))
        # q' = q_nope . Wkvb_K: products of bf16 are exact in float32 and
        # the sum stays there; the scores' scale rides the query
        q_abs = jnp.concatenate(
            [jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wkvb[..., :dn],
                        preferred_element_type=jnp.float32),
             q_rope[:, 0].astype(jnp.float32)], -1) / math.sqrt(dn + self.rope)
        q_abs = jnp.pad(q_abs, ((0, 0), (0, 0),
                                (0, pool.shape[-1] - q_abs.shape[-1])))
        o_lat, q_read = paged_mla_attention(q_abs, pool, page_table, pos,
                                            self.kv_rank,
                                            kernel=_single_tpu())
        n = min(h, TAP_HEADS)
        self.sow("routing", "mla_query", q_read[:, None, :n].reshape(b, 1, -1))
        self.sow("routing", "mla_q_nope", q_nope[:, :, :n].reshape(b, 1, -1))
        a = jnp.einsum("bhr,rhd->bhd", o_lat.astype(self.dtype),
                       wkvb[..., dn:], preferred_element_type=jnp.float32)
        return a[:, None], (pool,)


class _ShortcutBlock(nn.Module):
    """One block: two latent attentions, two dense MLPs, one routed layer
    on the shortcut.  `attn` / `sparse`: the two sublayers' settings."""

    attn: dict
    sparse: dict
    dense_width: int
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x, cache=None, pos=None, page_table=None, live=None,
                 lengths=None):
        """cache None: x [B, S, E] -> (x, the two attentions' rows);
        `lengths` [B]: the rows' own positions, for the attentions.
        Otherwise x [B, 1, E] at `pos` over the two sublayers' pools
        `cache` -> (x, the two sublayers' pools)."""
        dt = self.dtype
        caches = (None, None) if cache is None else cache

        def norm(name):
            return _RMSNorm(self.eps, dt, name=name)

        def attend(j, x):
            a, kept = _LatentAttention(dtype=dt, eps=self.eps,
                                       name=f"attn{j}", **self.attn)(
                norm(f"attn_norm{j}")(x), caches[j], pos, page_table,
                lengths=lengths)
            return x + a.astype(dt), kept

        x, kept0 = attend(0, x)
        y0 = norm("mlp_norm0")(x)
        shortcut = _SparseMLP(dtype=dt, name="moe", **self.sparse)(y0, live)
        with jax.named_scope("mlp.dense"):
            x = x + _DenseMLP(self.dense_width, dt, name="mlp0")(y0).astype(dt)
        x, kept1 = attend(1, x)
        with jax.named_scope("mlp.dense"):
            x = x + _DenseMLP(self.dense_width, dt, name="mlp1")(
                norm("mlp_norm1")(x)).astype(dt)
        return x + shortcut.astype(dt), (kept0, kept1)


class LongCatLM(nn.Module):
    """Decoder-only LM over int32 token ids [B, S]; defaults are a tiny
    preset, the published sizes come from a configuration file
    (benchmarks/configs/longcat-flash-chat.json through `from_config`)."""

    vocab_size: int = 128
    embed_dim: int = 64
    num_layers: int = 2             # blocks: two cached sublayers each
    num_heads: int = 4
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 16
    q_lora_rank: int = 32
    kv_lora_rank: int = 24
    dense_width: int = 128
    expert_width: int = 32
    num_experts: int = 8            # FFN experts, published count
    experts_held: Tuple[int, int] = (0, 8)
    zero_experts: int = 4           # identity experts behind them
    top_k: int = 3
    routed_scaling: float = 6.0
    rope_theta: float = 1e7
    eps: float = 1e-5
    max_len: int = 64
    dtype: Any = jnp.bfloat16
    layer_names = ["logits", "hidden", "embed"]
    input_dtype = jnp.int32
    stat_counters = counters_of(STAT_NAMES + (ZERO_STAT,))

    @classmethod
    def from_config(cls, cfg: dict, max_len: int, dtype=jnp.bfloat16):
        """The model of a LongCat `config.json` cut as its file says:
        `num_layers` leading blocks, `n_routed_experts` held of
        `published.n_routed_experts` (the first ones), `vocab_size`
        rows."""
        return cls(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            num_layers=cfg["num_layers"],
            num_heads=cfg["num_attention_heads"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            dense_width=cfg["ffn_hidden_size"],
            expert_width=cfg["expert_ffn_hidden_size"],
            num_experts=cfg["published"]["n_routed_experts"],
            experts_held=(0, cfg["n_routed_experts"]),
            zero_experts=cfg["zero_expert_num"], top_k=cfg["moe_topk"],
            routed_scaling=float(cfg["routed_scaling_factor"]),
            rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
            max_len=max_len, dtype=dtype)

    # ---- what the batcher reads ----------------------------------------
    @property
    def cache_kinds(self):
        return (("latent", None),)

    @property
    def layer_kinds(self):
        """Per CACHED sublayer (two a block), the index into
        `cache_kinds`."""
        return (0,) * (2 * self.num_layers)

    @property
    def attn_shapes(self):
        """Per cache kind, (query heads a KV head, q/k head width, v head
        width) of the admission's attention: the latent rows expanded to
        a K and a V head for every query head."""
        return ((1, self.qk_nope_dim + self.qk_rope_dim, self.v_head_dim),)

    @property
    def cache_rows(self):
        """One pool a sublayer: the latent and the rotated rope key, in
        rows of whole lane tiles."""
        return ((latent_row_width(self.kv_lora_rank, self.qk_rope_dim),),)

    # ---- the network -----------------------------------------------------
    def _block(self, i: int):
        attn = dict(heads=self.num_heads, nope=self.qk_nope_dim,
                    rope=self.qk_rope_dim, v_dim=self.v_head_dim,
                    q_rank=self.q_lora_rank, kv_rank=self.kv_lora_rank,
                    theta=self.rope_theta)
        sparse = dict(num_experts=self.num_experts, top_k=self.top_k,
                      width=self.expert_width, shared_width=0,
                      scaling=self.routed_scaling,
                      held=tuple(self.experts_held), renormalise=False,
                      choice_bias=True, zero_experts=self.zero_experts,
                      token_chunk=MOE_TOKEN_CHUNK)
        return _ShortcutBlock(attn, sparse, self.dense_width, self.eps,
                              self.dtype, name=f"layer{i}")

    def _embed(self, tokens):
        table = self.param("embed", _normal(1.0),
                           (self.vocab_size, self.embed_dim), self.dtype)
        return table[tokens]

    def _head(self, x):
        x = _RMSNorm(self.eps, self.dtype, name="final_norm")(x)
        w = self.param("head", _normal(self.embed_dim ** -0.5),
                       (self.embed_dim, self.vocab_size), self.dtype)
        return jnp.dot(x, w, preferred_element_type=jnp.float32)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        x = self._embed(tokens)
        taps = {"embed": x}
        for i in range(self.num_layers):
            x, _rows = self._block(i)(x)
        taps["hidden"] = x
        logits = self._head(x)
        taps["logits"] = logits
        return logits, taps

    @nn.compact
    def prefill(self, tokens, last):
        """tokens [K, S] (rows padded past their prompt), last [K] the
        index of each row's last prompt token -> (logits there [K, V]
        f32, per cached sublayer (rows [K, S, `latent_row_width`],))."""
        x = self._embed(tokens)
        live = jnp.arange(tokens.shape[1])[None] <= last[:, None]
        rows = []
        for i in range(self.num_layers):
            x, kept = self._block(i)(x, live=live, lengths=last + 1)
            rows.extend(kept)
        x_last = x[jnp.arange(x.shape[0]), last]
        return self._head(x_last), tuple(rows)

    @nn.compact
    def decode_step(self, token, cache, pos, page_table):
        """token [B, 1] at per-slot positions pos [B]; cache the
        per-sublayer pools (two a block); page_table one [B, MP] table
        per cache kind.  A slot parked at position 0 is nobody's.
        -> (logits [B, 1, V] f32, cache)."""
        x = self._embed(token)
        new_cache = []
        live = (pos > 0)[:, None]
        for i in range(self.num_layers):
            x, kept = self._block(i)(x, cache[2 * i:2 * i + 2], pos,
                                     page_table[0], live)
            new_cache.extend(kept)
        return self._head(x), tuple(new_cache)
