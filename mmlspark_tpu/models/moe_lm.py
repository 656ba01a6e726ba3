"""MoELM: a decoder-only LM of the Laguna family (poolside/Laguna-S-2.1):
RMSNorm pre-norm blocks, grouped-query attention with a per-head sigmoid
gate on its output, window and full attention layers of different head
counts and different rotary embeddings, a gated (SiLU) MLP, and from the
second layer on a dropless top-k of routed experts beside one shared
expert.

A module of its own beside `TransformerLM` (not further arms of its
block), with the same serving contract, so `ContinuousBatcher(model,
variables, paged=True)` serves either:

- `__call__(tokens)` -> (logits, taps): the plain forward;
- `decode_step(token [B, 1], cache, pos [B], page_table)` -> (logits,
  cache): one token a slot over PAGE POOLS, `page_table` one table per
  cache kind;
- `prefill(tokens [K, S], last [K])` -> (logits of each row's last
  position [K, V], per-layer (k, v) rows [K, S, Hkv*D]);
- `cache_kinds` / `layer_kinds`: the state each layer keeps — the whole
  context (`("full", None)`) or the last `window` positions
  (`("window", 512)`) — which is what the batcher sizes its pools, page
  tables and reservations from.

THE SHARE.  `experts_held = (lo, hi)` tells every routed layer which of
the `num_experts` it holds: the router scores all of them, the top-k is
over all of them, and the layer sums only the chosen experts it holds;
what the absent ones would add is left out and that partial sum goes on
(benchmarks/lib/reference_laguna.py computes the same).  The shared
expert, attention and norms are whole.  `vocab_size` is the slice held:
embedding and head have that many rows.

Routing statistics leave the programs through the `stats` collection
(`STAT_NAMES`, summed over the routed layers by whoever asks for the
collection; `stat_counters` names the counter each feeds).  The first two
count what the grouped matmul computes, padding and idle slots included;
the last two count the LIVE rows only (a prompt's own positions, slots at
a position past 0), which is what the load statistic is about.  A routed
layer offers what it read and computed to whoever asks for the `routing`
collection (a verifier): its input as the experts and as the router read
it, router logits, chosen experts, routed output.  Nobody asking is free.
"""
from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import grouped_matmul as gm
from ..ops.quant import rounded_to
from .transformer import _rope, _single_tpu

__all__ = ["MoELM", "STAT_NAMES", "yarn_inv_freq"]

STAT_NAMES = ("moe_assignments", "moe_experts_touched",
              "moe_live_assignments", "moe_load_max")
ZERO_STAT = "moe_zero_assignments"      # a layer with zero-compute experts


def counters_of(names):
    """((stat, the counter it feeds), ...): `moe_x` -> `serving.moe.x`."""
    return tuple((name, "serving.moe." + name[len("moe_"):])
                 for name in names)


def _router_logits(y, wr):
    """Router logits over ALL experts, in float32 whatever the model's
    dtype."""
    return jnp.dot(y.astype(jnp.float32), wr.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def yarn_inv_freq(rot_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float):
    """YaRN's blended inverse frequencies over `rot_dim` rotated
    dimensions: the plain 1 / theta^(2i/rot_dim) where a dimension turns
    more than beta_fast times over the original context, the same over
    `factor` where it turns fewer than beta_slow times, a linear ramp
    between the two correction dimensions."""
    def correction(turns):
        return (rot_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), rot_dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(rot_dim // 2, dtype=jnp.float32)
    plain = 1.0 / theta ** (2 * i / rot_dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rotate(x, positions, inv_freq, mscale: float):
    """Rotate the first 2 * len(inv_freq) dimensions of [B, S, H, D] by
    position x inv_freq (pairs (i, i + half), `_rope`'s convention), cos
    and sin times `mscale`; the rest passes."""
    half = inv_freq.shape[0]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if ang.ndim == 2:
        ang = ang[None]
    ang = ang[:, :, None, :]
    sin, cos = jnp.sin(ang) * mscale, jnp.cos(ang) * mscale
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x1 * sin + x2 * cos).astype(x.dtype), x[..., 2 * half:]], axis=-1)


def _normal(std):
    return nn.initializers.normal(stddev=std)


# router logits [T, X] float32 -> the scores the top-k is taken of
_SCORES = {"softmax": lambda r: jax.nn.softmax(r, axis=-1),
           "sigmoid": jax.nn.sigmoid}


class _RMSNorm(nn.Module):
    """`gain`: a constant the normed row is multiplied by in float32,
    before its one rounding to the model's dtype."""

    eps: float
    dtype: Any
    gain: float = 1.0
    param_dtype: Any = None     # what the scale is KEPT in (None: dtype)

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype or self.dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                + self.eps)
        y = y * w.astype(jnp.float32)
        if self.gain != 1.0:
            y = y * self.gain
        # one rounding, and the same for every consumer
        return rounded_to(y, self.dtype).astype(self.dtype)


class _DenseMLP(nn.Module):
    """`param_dtype`: what the weights are KEPT in (a trainer's float32
    under a bfloat16 `dtype`; None: `dtype`); they are read in `dtype`."""

    width: int
    dtype: Any
    param_dtype: Any = None

    @nn.compact
    def __call__(self, y):
        e = y.shape[-1]
        kept = self.param_dtype or self.dtype
        w1, w3, w2 = (
            self.param(name, _normal(shape[0] ** -0.5), shape, kept).astype(
                self.dtype)
            for name, shape in (("w1", (e, self.width)),
                                ("w3", (e, self.width)),
                                ("w2", (self.width, e))))
        h = jax.nn.silu(jnp.dot(y, w1, preferred_element_type=jnp.float32))
        h = h * jnp.dot(y, w3, preferred_element_type=jnp.float32)
        return jnp.dot(h.astype(y.dtype), w2,
                       preferred_element_type=jnp.float32)


def _zero_experts_term(y, weights, top_e, num_experts: int):
    """What the chosen zero-compute (identity) experts add: ids at or
    past `num_experts` hand their input back, so their part of the sum is
    the token's own row times the weights that fell on them.  No matmul,
    no dispatch: outside the grouped matmul.  -> [T, E] float32."""
    w_zero = jnp.sum(jnp.where(top_e >= num_experts, weights, 0.0), -1)
    return w_zero[:, None] * y.astype(jnp.float32)


class _SparseMLP(nn.Module):
    """The routed layer of every MoE model here.  A router over all
    `num_experts` FFN experts and then `zero_experts` identity experts,
    `scores` a softmax over them or a sigmoid of each, the top-k chosen
    by score (plus a selection `bias` that weighs nothing, if
    `choice_bias`), weights the chosen scores times `scaling`,
    renormalised over the top-k if `renormalise`; the FFN experts in
    [lo, hi) held here, the identity experts computed for every token,
    one shared expert if `shared_width`.  `token_chunk`: a call of more
    tokens sends them through the experts that many at a time, the last
    chunk padded with rows that fall on no expert (0: all at once).

    The selection bias is a parameter (drawn by whoever makes the
    weights), or with `bias_collection` a variable of that collection,
    starting at 0: STATE that a controller moves between optimizer steps
    (`models/glm_moe_lm.py`) and that no optimizer sees.  Either way no
    gradient reaches it.  For that controller the layer sows into `load`
    the live rows' assignments on each of ALL `num_experts +
    zero_experts` outputs, held here or not.  `param_dtype`: what the
    weights are kept in (None: `dtype`); the experts read them in
    `dtype`, the router as kept.  `train`: the experts' kernel arm that
    carries a backward."""

    num_experts: int
    top_k: int
    width: int
    shared_width: int
    scaling: float
    held: Tuple[int, int]
    dtype: Any
    renormalise: bool = True
    choice_bias: bool = False
    zero_experts: int = 0
    token_chunk: int = 0
    scores: str = "softmax"                 # | "sigmoid"
    bias_collection: Optional[str] = None
    param_dtype: Any = None

    @nn.compact
    def __call__(self, y, live=None, train: bool = False):
        """y [..., E]; live [...] bool: the rows that are somebody's
        tokens (None: all), for the load statistics only."""
        lead, e = y.shape[:-1], y.shape[-1]
        lo, hi = self.held
        n_held = hi - lo
        n_out = self.num_experts + self.zero_experts
        kept = self.param_dtype or self.dtype
        y = y.reshape(-1, e)
        wr = self.param("router", _normal(e ** -0.5), (e, n_out), kept)
        w1 = self.param("w1", _normal(e ** -0.5),
                        (n_held, e, self.width), kept).astype(self.dtype)
        w3 = self.param("w3", _normal(e ** -0.5),
                        (n_held, e, self.width), kept).astype(self.dtype)
        w2 = self.param("w2", _normal(self.width ** -0.5),
                        (n_held, self.width, e), kept).astype(self.dtype)
        with jax.named_scope("moe.route"):
            r = _router_logits(y, wr)
            p = _SCORES[self.scores](r)
            if self.choice_bias:
                if self.bias_collection:
                    bias = self.variable(
                        self.bias_collection, "bias",
                        lambda: jnp.zeros((n_out,), jnp.float32)).value
                else:
                    bias = self.param("bias", nn.initializers.zeros,
                                      (n_out,), jnp.float32)
                # the biased scores choose and weigh nothing
                _biased, top_e = jax.lax.top_k(
                    p + jax.lax.stop_gradient(bias), self.top_k)
                top_p = jnp.take_along_axis(p, top_e, -1)
            else:
                top_p, top_e = jax.lax.top_k(p, self.top_k)
            weights = self.scaling * top_p
            if self.renormalise:
                total = jnp.sum(top_p, -1, keepdims=True)
                if self.scores == "sigmoid":    # scores that can all be 0
                    total = total + 1e-20
                weights = weights / total
        alive = (jnp.ones(top_e.shape[:1], bool) if live is None
                 else live.reshape(-1))

        def experts(y, top_e, weights, alive):
            """The held experts' weighted sum for these rows, and the
            rows' statistics."""
            tm = gm.row_tile(y.shape[0])
            plan = gm.dispatch(top_e.astype(jnp.int32), lo, hi, tm)
            rows = gm.expert_mlp(y, plan, w1, w3, w2, tm,
                                 kernel=_single_tpu(), train=train)
            if live is None:
                load = plan.counts
            else:                   # the same count over the live rows
                e_live = jnp.where(plan.held & alive[:, None], top_e - lo,
                                   n_held)
                load = jnp.zeros(n_held + 1, jnp.int32).at[e_live].add(
                    1)[:n_held]
            return gm.combine(rows, plan, weights), (
                jnp.sum(plan.counts), jnp.sum(plan.counts > 0), load)

        with jax.named_scope("moe.experts"):
            t, chunk = y.shape[0], self.token_chunk
            if chunk and t > chunk:
                # the dispatch buffers hold a row for EVERY assignment
                # of the call (dropless, whatever falls on the experts
                # held): a long admission goes through in chunks of
                # tokens, so that they stay the size of one chunk's
                rows = (y, top_e, weights, alive)
                pad = -t % chunk
                if pad:             # the last chunk's rows past the call:
                    # nobody's, and on an expert no chip holds
                    rows = tuple(
                        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                                constant_values=fill)
                        for a, fill in zip(rows, (0, n_out, 0, False)))
                out, (assigned, touched, load) = jax.lax.map(
                    lambda a: experts(*a), jax.tree.map(
                        lambda a: a.reshape(-1, chunk, *a.shape[1:]), rows))
                out = out.reshape(-1, e)
                if pad:
                    out = out[:t]
                assigned, touched, load = (jnp.sum(assigned), jnp.sum(touched),
                                           jnp.sum(load, 0))
            else:
                out, (assigned, touched, load) = experts(y, top_e, weights,
                                                         alive)
        if self.zero_experts:
            with jax.named_scope("moe.zero"):
                out = out + _zero_experts_term(y, weights, top_e,
                                               self.num_experts)
        taps = dict(input=y, router_input=y.astype(jnp.float32), logits=r,
                    experts=top_e, routed=out)    # for whoever asks
        for name, value in taps.items():
            self.sow("routing", name, value.reshape(*lead, -1))
        if self.shared_width:
            with jax.named_scope("moe.shared"):
                out = out + _DenseMLP(self.shared_width, self.dtype,
                                      self.param_dtype, name="shared")(y)
        # the controller's reading: live assignments on every output
        chosen = (top_e[..., None] == jnp.arange(n_out)) & alive[:, None, None]
        self.sow("load", "counts", jnp.sum(chosen, (0, 1), dtype=jnp.int32),
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((n_out,), jnp.int32))
        stats = [assigned, touched, jnp.sum(load), jnp.max(load)]
        names = STAT_NAMES
        if self.zero_experts:       # live assignments that cost nothing
            names = names + (ZERO_STAT,)
            stats.append(jnp.sum((top_e >= self.num_experts)
                                 & alive[:, None]))
        for name, value in zip(names, stats):
            self.sow("stats", name, value.astype(jnp.int32),
                     reduce_fn=lambda a, b: a + b,
                     init_fn=lambda: jnp.zeros((), jnp.int32))
        return out.reshape(*lead, e)


class _Block(nn.Module):
    """One layer.  `kind`: index into the model's cache kinds; `window`:
    None on a full layer."""

    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]
    rope: Tuple      # (theta,) plain | (theta, part, factor, ...) YaRN
    sparse: Optional[dict]
    dense_width: int
    eps: float
    dtype: Any

    def _rope(self, x, positions):
        if len(self.rope) == 1:
            return _rope(x, positions, self.rope[0])
        theta, part, factor, orig, fast, slow, mscale = self.rope
        inv = yarn_inv_freq(int(self.head_dim * part), theta, factor, orig,
                            fast, slow)
        return _rotate(x, positions, inv, mscale)

    @nn.compact
    def __call__(self, x, cache=None, pos=None, page_table=None, live=None,
                 lengths=None):
        """cache None: causal (windowed) attention over x [B, S, E], of
        each row its first `lengths` [B] positions (None: all; the rest
        is an admission bucket's padding and attends nothing);
        returns (x, (k, v) rows [B, S, Hkv*D]).  Otherwise x is [B, 1, E]
        at per-slot `pos` [B] and cache this layer's page pools
        ([NP, page, Hkv*D] each) under `page_table` [B, MP] (a ring of
        window / page + 1 entries on a window layer); returns (x,
        pools).  `live` [B, S] bool: the rows that are somebody's tokens
        (the routed layer's load statistics)."""
        b, s, e = x.shape
        h, hkv, d = self.heads, self.kv_heads, self.head_dim
        dt = self.dtype

        def proj(name, n_in, n_out):
            return self.param(name, _normal(n_in ** -0.5), (n_in, n_out), dt)

        y = _RMSNorm(self.eps, dt, name="attn_norm")(x)
        q = jnp.dot(y, proj("wq", e, h * d)).reshape(b, s, h, d)
        k = jnp.dot(y, proj("wk", e, hkv * d)).reshape(b, s, hkv, d)
        v = jnp.dot(y, proj("wv", e, hkv * d)).reshape(b, s, hkv, d)
        gate = jax.nn.sigmoid(jnp.dot(
            y, proj("wg", e, h), preferred_element_type=jnp.float32))
        positions = (jnp.arange(s) if cache is None
                     else pos[:, None] + jnp.arange(s)[None])
        q, k = self._rope(q, positions), self._rope(k, positions)
        scope = "attn.window" if self.window is not None else "attn.full"
        with jax.named_scope(scope):
            if cache is None:
                from ..ops.attention_kernels import prefill_attention

                a = prefill_attention(q, k, v, self.window, lengths,
                                      kernel=_single_tpu())
                cache = (k.reshape(b, s, hkv * d), v.reshape(b, s, hkv * d))
            else:
                a, cache = self._paged(q, k, v, cache, pos, page_table)
        a = (a * gate[..., None]).astype(dt).reshape(b, s, h * d)
        x = x + jnp.dot(a, proj("wo", h * d, e))
        y = _RMSNorm(self.eps, dt, name="mlp_norm")(x)
        if self.sparse is None:
            m = _DenseMLP(self.dense_width, dt, name="mlp")(y)
        else:
            m = _SparseMLP(dtype=dt, name="moe", **self.sparse)(y, live)
        return x + m.astype(dt), cache

    def _paged(self, q, k, v, cache, pos, page_table):
        """Write this token's K/V row into its page, then attend: the
        page walk on one TPU, the gather composition elsewhere."""
        from ..ops.paged_attention import (_xla_paged, _xla_paged_window,
                                           paged_decode_attention)

        b, s, h, d = q.shape
        if s != 1:
            raise NotImplementedError(
                "MoELM decodes one token a slot over its page pools; block "
                "decode (speculation, shared prefixes) is not built")
        k_pool, v_pool = cache
        page, mp = k_pool.shape[1], page_table.shape[1]
        lp = pos // page
        entry = lp % mp if self.window is not None else jnp.minimum(lp, mp - 1)
        pg = page_table[jnp.arange(b), entry]
        off = pos % page
        k_pool = k_pool.at[pg, off].set(
            k.reshape(b, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[pg, off].set(
            v.reshape(b, -1).astype(v_pool.dtype))
        if _single_tpu():
            a = paged_decode_attention(q[:, 0], k_pool, v_pool, page_table,
                                       pos, window=self.window)
        elif self.window is not None:
            a = _xla_paged_window(q[:, 0], k_pool, v_pool, page_table, pos,
                                  self.window)
        else:
            a = _xla_paged(q[:, 0], k_pool, v_pool, page_table, pos)
        return a[:, None], (k_pool, v_pool)


class MoELM(nn.Module):
    """Decoder-only LM over int32 token ids [B, S]; defaults are a tiny
    preset, the published sizes come from a configuration file
    (benchmarks/configs/laguna-s-2.1.json through `MoELM.from_config`)."""

    vocab_size: int = 128
    embed_dim: int = 64
    head_dim: int = 16
    kv_heads: int = 1
    # per layer: query heads, "full" | "window", "dense" | "sparse"
    layer_heads: Sequence[int] = (2, 3)
    layer_types: Sequence[str] = ("full", "window")
    mlp_types: Sequence[str] = ("dense", "sparse")
    window: int = 8
    dense_width: int = 128
    num_experts: int = 8
    experts_held: Tuple[int, int] = (0, 8)
    top_k: int = 2
    expert_width: int = 32
    shared_width: int = 32
    routed_scaling: float = 2.5
    rope_window_theta: float = 10000.0
    # YaRN on the full layers' first `rotary_factor` of the head
    rope_full: Tuple = (500000.0, 0.5, 128.0, 8192, 32.0, 1.0,
                        1.4852030263919618)
    eps: float = 1e-6
    max_len: int = 64
    dtype: Any = jnp.bfloat16
    layer_names = ["logits", "hidden", "embed"]
    input_dtype = jnp.int32
    # the `stats` a program sums, and the counter each feeds when a
    # server hands them back
    stat_counters = counters_of(STAT_NAMES)

    @classmethod
    def from_config(cls, cfg: dict, max_len: int, dtype=jnp.bfloat16):
        """The model of a Laguna `config.json` cut as its file says:
        `num_hidden_layers` leading layers, `num_experts` held of
        `published.num_experts` (the first ones), `vocab_size` rows."""
        n = cfg["num_hidden_layers"]
        rp = cfg["rope_parameters"]
        full, win = rp["full_attention"], rp["sliding_attention"]
        return cls(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            head_dim=cfg["head_dim"], kv_heads=cfg["num_key_value_heads"],
            layer_heads=tuple(cfg["num_attention_heads_per_layer"][:n]),
            layer_types=tuple("window" if t == "sliding_attention" else "full"
                              for t in cfg["layer_types"][:n]),
            mlp_types=tuple(cfg["mlp_layer_types"][:n]),
            window=cfg["sliding_window"],
            dense_width=cfg["intermediate_size"],
            num_experts=cfg["published"]["num_experts"],
            experts_held=(0, cfg["num_experts"]),
            top_k=cfg["num_experts_per_tok"],
            expert_width=cfg["moe_intermediate_size"],
            shared_width=cfg["shared_expert_intermediate_size"],
            routed_scaling=cfg["moe_routed_scaling_factor"],
            rope_window_theta=float(win["rope_theta"]),
            rope_full=(float(full["rope_theta"]),
                       full["partial_rotary_factor"], float(full["factor"]),
                       full["original_max_position_embeddings"],
                       float(full["beta_fast"]), float(full["beta_slow"]),
                       full["attention_factor"]),
            eps=cfg["rms_norm_eps"], max_len=max_len, dtype=dtype)

    # ---- what the batcher reads ----------------------------------------
    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_width(self) -> int:
        """Width of one K (or V) cache row: the KV heads side by side."""
        return self.kv_heads * self.head_dim

    @property
    def cache_kinds(self):
        """((name, window), ...): the kinds of KV state the layers keep."""
        kinds = [("full", None)]
        if "window" in self.layer_types:
            kinds.append(("window", self.window))
        return tuple(kinds)

    @property
    def cache_rows(self):
        """Per cache kind, the row width of each pool a layer of the
        kind keeps: a K and a V pool."""
        return ((self.kv_width, self.kv_width),) * len(self.cache_kinds)

    @property
    def layer_kinds(self):
        """Per layer, the index into `cache_kinds`."""
        return tuple(0 if t == "full" else 1 for t in self.layer_types)

    @property
    def attn_shapes(self):
        """Per cache kind, (query heads a KV head, q/k head width, v head
        width) of its (first) layer's admission attention: what
        `prefill_attention` is called at, for the batcher's count of its
        tiles."""
        kinds = self.layer_kinds
        return tuple((self.layer_heads[kinds.index(kind)] // self.kv_heads,
                      self.head_dim, self.head_dim)
                     for kind in range(len(self.cache_kinds)))

    # ---- the network -----------------------------------------------------
    def _block(self, i: int):
        windowed = self.layer_types[i] == "window"
        rope = ((self.rope_window_theta,) if windowed
                else tuple(self.rope_full))
        sparse = None
        if self.mlp_types[i] == "sparse":
            sparse = dict(num_experts=self.num_experts, top_k=self.top_k,
                          width=self.expert_width,
                          shared_width=self.shared_width,
                          scaling=self.routed_scaling,
                          held=tuple(self.experts_held))
        return _Block(self.layer_heads[i], self.kv_heads, self.head_dim,
                      self.window if windowed else None, rope, sparse,
                      self.dense_width, self.eps, self.dtype,
                      name=f"layer{i}")

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
        f32, per-layer (k, v) rows [K, S, Hkv*D]).  Only those K rows
        meet the head: S x V logits of every position are never made."""
        x = self._embed(tokens)
        live = jnp.arange(tokens.shape[1])[None] <= last[:, None]
        rows = []
        for i in range(self.num_layers):
            x, kv = self._block(i)(x, live=live, lengths=last + 1)
            rows.append(kv)
        x_last = x[jnp.arange(x.shape[0]), last]
        return self._head(x_last), tuple(rows)

    @nn.compact
    def decode_step(self, token, cache, pos, page_table):
        """token [B, 1] at per-slot positions pos [B]; cache the
        per-layer page pools; page_table one [B, MP_kind] table per cache
        kind.  A slot parked at position 0 is nobody's (a prompt has at
        least one token).  -> (logits [B, 1, V] f32, cache)."""
        x = self._embed(token)
        new_cache = []
        kinds = self.layer_kinds
        live = (pos > 0)[:, None]
        for i in range(self.num_layers):
            x, pools = self._block(i)(x, cache[i], pos,
                                      page_table[kinds[i]], live)
            new_cache.append(pools)
        return self._head(x), tuple(new_cache)
