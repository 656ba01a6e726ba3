"""TransformerLM: a decoder-only language model with pluggable attention —
dense causal on one chip, exact ring attention over the mesh 'seq' axis for
long sequences.

Beyond-reference capability (the reference's longest-sequence handling is
the CNTK BiLSTM notebook, SURVEY §2.10 last row): sequence parallelism is
first-class here, so the same module trains/scans on contexts far longer
than one chip's HBM by sharding S over the mesh.  The attention
implementation is a constructor argument, not a fork of the model — the
parameters and numerics are identical either way (ring attention is exact,
parallel/ring_attention.py), which the tests assert.

TPU-first: bfloat16 compute / float32 params, pre-LN blocks (stable in low
precision), all shapes static under jit.  Named taps follow the zoo
contract: taps[layer_names[1]] ("pool", mean-pooled final hidden state) is
the penultimate feature for TPUModel / TrainClassifier composition.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

__all__ = ["TransformerLM", "transformer_lm"]


def _cache_attention(q, k_cache, v_cache, q_pos, d,
                     k_scale=None, v_scale=None):
    """s queries over a [B, L, H, D] cache, query (b, i) masked to cache
    positions <= q_pos[b, i] (q_pos broadcasts over B for the scalar-pos
    callers).  The one score/mask/softmax implementation every decode
    branch shares.  With k_scale/v_scale [B, L, H] the cache is int8 and
    the per-(pos, head) scale — constant over d — is factored OUT of the
    contractions: the dot operands stay pure int8->f32 converts (which
    fuse into the dot's read) and the scales multiply the tiny
    [B, H, s, L] score/prob tensors; no dequantized full-size cache is
    ever materialized."""
    quant = k_scale is not None
    sc = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32) if quant else q,
        k_cache.astype(jnp.float32) if quant else k_cache,
        preferred_element_type=jnp.float32)
    if quant:
        sc = sc * k_scale.transpose(0, 2, 1)[:, :, None, :]
    sc = sc / jnp.sqrt(jnp.float32(d))
    valid = (jnp.arange(k_cache.shape[1])[None, None, :]
             <= q_pos[:, :, None])                       # [B|1, s, L]
    sc = jnp.where(valid[:, None, :, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    if quant:
        p = p * v_scale.transpose(0, 2, 1)[:, :, None, :]
        return jnp.einsum("bhqk,bkhd->bqhd", p,
                          v_cache.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_cache.dtype), v_cache,
                      preferred_element_type=jnp.float32)


def _rope(x, positions, base: float = 10000.0):
    """Rotary position embedding: rotate [..., S, H, D] q/k by per-position
    angles.  `positions` is [S] (shared) or [B, S] (per-row, slot decode).
    Relative by construction — attention scores depend only on position
    DIFFERENCES, so decode at any cache offset matches the full forward
    (rotated keys are what the KV cache stores)."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / (base ** (jnp.arange(d2, dtype=jnp.float32) / d2))
    ang = positions.astype(jnp.float32)[..., None] * inv
    if ang.ndim == 2:                      # [S, d2] -> broadcast over B
        ang = ang[None]
    ang = ang[:, :, None, :]               # [B|1, S, 1, d2]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _gqa_expand(kv, num_heads: int):
    """[..., Hkv, D] or [..., Hkv] K/V (or scales) -> repeated to
    num_heads along the head axis (no-op for MHA).  The cache STORES Hkv
    heads — this expansion happens at attention-read time, where XLA can
    fold the broadcast into the einsum's gather."""
    axis = kv.ndim - 2 if kv.ndim >= 4 else kv.ndim - 1
    reps = num_heads // kv.shape[axis]
    if reps == 1:
        return kv
    return jnp.repeat(kv, reps, axis=axis)


def _single_tpu() -> bool:
    """Default-attention / paged-decode dispatch predicate: the
    computation being traced targets exactly ONE TPU device
    (ops.pallas_kernels.on_single_tpu).  Separable so tests can force
    the Pallas branch on the CPU backend via interpret mode."""
    from ..ops.pallas_kernels import on_single_tpu

    return on_single_tpu()


def default_attn(causal: bool):
    """The default-attention dispatch shared by TransformerLM and ViT:
    the Pallas kernel pair (VMEM-resident scores forward, flash
    backward) on a single TPU, where dense XLA's f32 [B, H, S, S] score
    traffic is pure HBM waste; XLA dense under GSPMD sharding (a Pallas
    custom call is not partitionable).  Sequence-parallel users pass
    ring/ulysses attn_fns instead, which shard_map themselves."""
    if _single_tpu():
        from ..ops.attention_kernels import fused_attention

        return lambda q, k, v: fused_attention(q, k, v, causal)
    from ..parallel.ring_attention import full_attention

    return lambda q, k, v: full_attention(q, k, v, causal=causal)


class _MoEMLP(nn.Module):
    """Switch-style top-1 mixture-of-experts MLP — the expert-parallel
    ('ep') building block.  TPU-idiomatic dispatch: routing is one-hot
    einsum dispatch/combine tensors (no ragged gathers; static [X, C, E]
    expert buffers), so sharding the expert dimension of w_in/w_out over
    a mesh axis makes XLA insert the all_to_alls — expert parallelism
    falls out of shardings, exactly like dp/tp.

    Tokens beyond an expert's capacity are dropped (their block output is
    0 and the residual carries them — the Switch Transformer contract).
    The load-balance aux loss (num_experts * sum(frac_tokens * mean_prob))
    is sown into the 'losses' collection; training factories add every
    sown loss to the objective."""

    num_experts: int
    mlp_ratio: int
    dtype: Any
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x):
        b, s, e = x.shape
        nx = self.num_experts
        # capacity binds PER ROW: a sequence's routing must not depend on
        # its batch co-tenants (batched scoring and continuous-batching
        # slot decode both promise row independence)
        cap = max(1, int(self.capacity_factor * s / nx))
        logits = nn.Dense(nx, dtype=jnp.float32,
                          name="router")(x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                 # [B, S, X]
        expert = jnp.argmax(probs, axis=-1)                     # [B, S]
        gate = jnp.max(probs, axis=-1)                          # [B, S]
        onehot = jax.nn.one_hot(expert, nx)                     # [B, S, X]
        # position of each token in its row's expert queue; beyond-cap
        # tokens drop
        pos = (jnp.sum(jnp.cumsum(onehot, axis=1) * onehot, axis=-1)
               .astype(jnp.int32) - 1)
        keep = (pos < cap) & (pos >= 0)
        disp = (onehot[..., None] * jax.nn.one_hot(pos, cap)[:, :, None, :]
                * keep[..., None, None])                     # [B, S, X, C]
        disp = disp.astype(self.dtype)
        w_in = self.param("w_in", nn.initializers.lecun_normal(),
                          (nx, e, self.mlp_ratio * e), jnp.float32)
        w_out = self.param("w_out", nn.initializers.lecun_normal(),
                           (nx, self.mlp_ratio * e, e), jnp.float32)
        buf = jnp.einsum("bse,bsxc->bxce", x.astype(self.dtype), disp)
        h = nn.gelu(jnp.einsum("bxce,xeh->bxch", buf,
                               w_in.astype(self.dtype)))
        y = jnp.einsum("bxch,xhe->bxce", h, w_out.astype(self.dtype))
        out = jnp.einsum("bxce,bsxc->bse", y, disp) * gate[..., None].astype(
            self.dtype)
        # Switch load-balance loss: differentiable through mean_prob
        frac = jnp.mean(onehot, axis=(0, 1))                    # [X]
        mean_prob = jnp.mean(probs, axis=(0, 1))                # [X]
        self.sow("losses", "moe_aux", nx * jnp.sum(frac * mean_prob))
        return out


class _Block(nn.Module):
    num_heads: int
    mlp_ratio: int
    dtype: Any
    attn_fn: Callable
    # grouped-query attention: kv_heads < num_heads shares each K/V head
    # across num_heads//kv_heads query heads — the KV cache (the decode
    # HBM bottleneck) shrinks by the same factor.  None = MHA; the fused
    # qkv projection (and its param pytree) is kept in that case.
    kv_heads: Optional[int] = None
    # injection point for quantized inference (ops/quant.QuantDense): same
    # param pytree as nn.Dense, so trained weights serve either class
    dense_cls: Any = nn.Dense
    # > 0: the MLP is a switch-style mixture of that many experts
    num_experts: int = 0
    moe_capacity: float = 1.25
    # rotate q/k instead of relying on learned absolute embeddings
    rope: bool = False

    @nn.compact
    def __call__(self, x, cache=None, pos=None, page_table=None):
        """cache=None: full causal attention over x (train/score path).

        cache=(k_cache, v_cache) [B, max_len, Hkv, D] (Hkv = kv_heads
        or H — GQA caches store the SHARED heads) with scalar `pos`:
        block decode — x is [B, s, E] holding tokens at positions
        pos..pos+s-1 (s=1 is plain autoregressive decode); their K/V is
        written at `pos` (lax.dynamic_update_slice keeps shapes static)
        and query i attends over cache positions <= pos+i.  Returns
        (out, cache).

        cache=(kq, ks, vq, vs): int8-quantized variant — kq/vq are int8
        [B, max_len, Hkv, D] with per-row-per-head f32 scales ks/vs
        [B, max_len, Hkv].  The cache read is 1/4 the HBM bytes of f32 (1/2
        of bf16) and long-context decode is cache-bandwidth-bound; the
        dequant multiply fuses into the attention matmul's read.

        page_table [B, MP] int32 (slot decode only): the cache tuples are
        FLAT PAGE POOLS [NP, page, Hkv*D] (+[NP, page, Hkv] scales for
        int8) instead of per-slot rows — slot b's logical cache position p
        lives at pool[page_table[b, p // page], p % page].  The heads are
        folded into the minor axis because that is the shape whose default
        device layout the row scatter and the page-walk kernel both read:
        a donated pool is then updated in place (ops/paged_attention.py).
        Physical page 0 is the write-trash page: unallocated table entries
        point at it, so a free slot's dead write can never corrupt a live
        slot's pages, and gathered trash rows sit at logical positions
        > pos where the validity mask already hides them.
        """
        b, s, e = x.shape
        h = self.num_heads
        d = e // h
        hkv = self.kv_heads or h
        y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        if hkv == h:
            qkv = self.dense_cls(3 * e, use_bias=False, dtype=self.dtype,
                                 name="qkv")(y)
            q, k, v = jnp.split(qkv.reshape(b, s, 3 * h, d), 3, axis=2)
        else:
            q = self.dense_cls(e, use_bias=False, dtype=self.dtype,
                               name="q")(y).reshape(b, s, h, d)
            kv = self.dense_cls(2 * hkv * d, use_bias=False,
                                dtype=self.dtype,
                                name="kv")(y).reshape(b, s, 2 * hkv, d)
            k, v = jnp.split(kv, 2, axis=2)
        if self.rope:
            if cache is None:
                rp = jnp.arange(s)
            elif pos is not None and jnp.ndim(pos) == 1:
                # per-slot positions; s>1 = slot BLOCK decode, row b's
                # tokens sit at pos[b]..pos[b]+s-1
                rp = pos[:, None] + jnp.arange(s)[None]
            else:
                rp = pos + jnp.arange(s)
            q = _rope(q, rp)
            k = _rope(k, rp)
        if cache is None:
            # expose this layer's K/V to generation prefill (a no-op
            # unless the caller asked for the 'kvcache' collection)
            self.sow("kvcache", "k", k)
            self.sow("kvcache", "v", v)
            # q/k/v stay at model dtype so the attention matmuls hit the
            # MXU at full bf16 rate; the attention fns accumulate in f32
            # via preferred_element_type with f32 softmax statistics
            # (GQA: k/v repeat up to H here — the attn_fn contract wants
            # matching heads; the CACHE below stays at hkv)
            a = self.attn_fn(q, _gqa_expand(k, h), _gqa_expand(v, h))
        elif pos is not None and jnp.ndim(pos) == 1:
            # SLOT decode (continuous batching): x is [B, s, E], pos [B] —
            # every slot sits at its OWN position (requests admitted at
            # different times).  s=1 is the per-tick autoregressive step;
            # s>1 is slot BLOCK decode (per-slot speculative verification
            # / chunked prefill): row b's tokens occupy positions
            # pos[b]..pos[b]+s-1, query i masked to <= pos[b]+i.  Writes
            # are per-row scatters; the int8 4-tuple cache quantizes each
            # written row exactly like the scalar path, so slot decode
            # with int8 matches generate's int8 decode bit for bit (4x
            # the co-tenant density per HBM byte).
            rows_b = jnp.arange(b)
            rows_mat = rows_b[:, None]                         # [B, 1]
            posmat = pos[:, None] + jnp.arange(s)[None]        # [B, s]
            if page_table is not None:
                # PAGED slot decode: write one row into the owning page of
                # the flat [NP, page, Hkv*D] pools; s=1 on one TPU walks
                # the page table in a Mosaic kernel, everything else (block
                # decode, GQA pools) gathers each slot's pages back into a
                # logical [B, L, Hkv, D] view for the shared masked
                # attention.  Storage is pay-per-page (the
                # continuous-batching density win).
                page = cache[0].shape[1]
                mp = page_table.shape[1]
                # block positions past the table (bucket padding in a
                # suffix prefill) must write to the TRASH page — the
                # gather's default clamp would alias them onto the last
                # REAL page and corrupt live rows
                in_range = posmat < mp * page
                pgmat = jnp.where(
                    in_range,
                    page_table[rows_mat,
                               jnp.minimum(posmat // page, mp - 1)],
                    0)                                         # [B, s]
                offmat = posmat % page

                from ..ops.paged_attention import _gather_pages

                def gathered(pool, width=None):
                    # the slots' logical [B, L, H(, D)] view of a pool
                    return _gqa_expand(
                        _gather_pages(pool, page_table, width), h)

                if len(cache) == 4:
                    from ..ops.quant import quantize_kv_row

                    kq, ks, vq, vs = cache
                    knew, ksc = quantize_kv_row(k)
                    vnew, vsc = quantize_kv_row(v)
                    kq = kq.at[pgmat, offmat].set(knew.reshape(b, s, -1))
                    ks = ks.at[pgmat, offmat].set(ksc)
                    vq = vq.at[pgmat, offmat].set(vnew.reshape(b, s, -1))
                    vs = vs.at[pgmat, offmat].set(vsc)
                    cache = (kq, ks, vq, vs)
                    if s == 1 and _single_tpu():
                        # dispatch owned by ops.paged_attention (see the
                        # f32 branch below) — int8 page walk reads 1/4
                        # the HBM bytes of f32 AND only live pages
                        from ..ops.paged_attention import (
                            paged_decode_attention_int8)

                        a = paged_decode_attention_int8(
                            q[:, 0], kq, ks, vq, vs, page_table,
                            pos)[:, None]
                    else:
                        a = _cache_attention(
                            q, gathered(kq, d), gathered(vq, d),
                            posmat, d, k_scale=gathered(ks),
                            v_scale=gathered(vs))
                else:
                    k_pool, v_pool = cache
                    k_pool = k_pool.at[pgmat, offmat].set(
                        k.reshape(b, s, -1).astype(k_pool.dtype))
                    v_pool = v_pool.at[pgmat, offmat].set(
                        v.reshape(b, s, -1).astype(v_pool.dtype))
                    cache = (k_pool, v_pool)
                    if s == 1 and _single_tpu():
                        # paged_decode_attention owns kernel-vs-gather
                        # dispatch (shape/VMEM gate + GQA expansion):
                        # eligible shapes take the Mosaic page walk —
                        # cache reads scale with LIVE pages — the rest
                        # ride its XLA gather, same numerics
                        from ..ops.paged_attention import (
                            paged_decode_attention)

                        a = paged_decode_attention(
                            q[:, 0], k_pool, v_pool, page_table,
                            pos)[:, None]
                    else:
                        a = _cache_attention(
                            q, gathered(k_pool, d), gathered(v_pool, d),
                            posmat, d)
            elif len(cache) == 4:
                from ..ops.quant import quantize_kv_row

                kq, ks, vq, vs = cache
                knew, ksc = quantize_kv_row(k)
                vnew, vsc = quantize_kv_row(v)
                kq = kq.at[rows_mat, posmat].set(knew)
                ks = ks.at[rows_mat, posmat].set(ksc)
                vq = vq.at[rows_mat, posmat].set(vnew)
                vs = vs.at[rows_mat, posmat].set(vsc)
                cache = (kq, ks, vq, vs)
                a = _cache_attention(q, _gqa_expand(kq, h),
                                     _gqa_expand(vq, h), posmat, d,
                                     k_scale=_gqa_expand(ks, h),
                                     v_scale=_gqa_expand(vs, h))
            else:
                k_cache, v_cache = cache
                k_cache = k_cache.at[rows_mat, posmat].set(
                    k.astype(k_cache.dtype))
                v_cache = v_cache.at[rows_mat, posmat].set(
                    v.astype(v_cache.dtype))
                cache = (k_cache, v_cache)
                a = _cache_attention(q, _gqa_expand(k_cache, h),
                                     _gqa_expand(v_cache, h),
                                     posmat, d)
        elif len(cache) == 4:
            from ..ops.quant import quantize_kv_row

            kq, ks, vq, vs = cache
            knew, ksc = quantize_kv_row(k)
            vnew, vsc = quantize_kv_row(v)
            kq = jax.lax.dynamic_update_slice(kq, knew, (0, pos, 0, 0))
            ks = jax.lax.dynamic_update_slice(ks, ksc, (0, pos, 0))
            vq = jax.lax.dynamic_update_slice(vq, vnew, (0, pos, 0, 0))
            vs = jax.lax.dynamic_update_slice(vs, vsc, (0, pos, 0))
            cache = (kq, ks, vq, vs)
            a = _cache_attention(q, _gqa_expand(kq, h), _gqa_expand(vq, h),
                                 (pos + jnp.arange(s))[None], d,
                                 k_scale=_gqa_expand(ks, h),
                                 v_scale=_gqa_expand(vs, h))
        else:
            k_cache, v_cache = cache
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
            cache = (k_cache, v_cache)
            # s queries over the whole (static-length) cache, each masked
            # to its own position: an [s, max_len] matmul per head
            a = _cache_attention(q, _gqa_expand(k_cache, h),
                                 _gqa_expand(v_cache, h),
                                 (pos + jnp.arange(s))[None], d)
        a = a.astype(self.dtype).reshape(b, s, e)
        x = x + self.dense_cls(e, use_bias=False, dtype=self.dtype,
                               name="proj")(a)
        y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        if self.num_experts > 0:
            out = x + _MoEMLP(self.num_experts, self.mlp_ratio, self.dtype,
                              capacity_factor=self.moe_capacity,
                              name="moe")(y)
        else:
            y = self.dense_cls(self.mlp_ratio * e, dtype=self.dtype,
                               name="mlp_in")(y)
            y = nn.gelu(y)
            out = x + self.dense_cls(e, dtype=self.dtype, name="mlp_out")(y)
        return out if cache is None else (out, cache)


class TransformerLM(nn.Module):
    """Decoder-only LM over int32 token ids [B, S]."""

    vocab_size: int = 1024
    embed_dim: int = 128
    num_layers: int = 2
    num_heads: int = 4
    max_len: int = 2048
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    # None -> dense causal attention; or any (q, k, v) -> out with
    # (B, S, H, D) shapes, e.g. partial(ring_attention, mesh=m, causal=True).
    # PRECISION CONTRACT: q/k/v arrive at the MODEL dtype (bf16 when
    # dtype=bf16) so attention matmuls hit the MXU at full rate — the fn
    # must accumulate in f32 itself (preferred_element_type + f32 softmax
    # stats, as full_attention/ring_attention/ulysses_attention all do)
    # and should return f32.
    attn_fn: Optional[Callable] = None
    # int8 inference (ops/quant.py): block + head matmuls run as int8 on
    # the MXU.  Inference-only (round() kills gradients); pairs with
    # prequantize() for weight-bandwidth-bound batch-1 decode, where int8
    # weight reads are the whole game.
    quant: bool = False
    # > 0: every block's MLP is a switch-style top-1 mixture of this many
    # experts (expert-parallel over the mesh when w_in/w_out are sharded
    # on their leading dim; aux load-balance loss sown as 'losses')
    moe_experts: int = 0
    # capacity factor: tokens per expert = cap_factor * T / experts;
    # over-capacity tokens are dropped (residual carries them).  NOTE:
    # capacity binds per forward call, so a full forward that drops
    # tokens is not bit-identical to incremental decode (which never
    # fills a 1-token step's capacity) — raise it (e.g. >= experts) for
    # drop-free inference when decode/forward consistency matters.
    moe_capacity: float = 1.25
    # "learned" absolute position table, or "rope" rotary q/k (relative;
    # the long-context-friendly choice — no table capped at max_len)
    pos_emb: str = "learned"
    # grouped-query attention: None = MHA; otherwise the number of shared
    # K/V heads (must divide num_heads) — the KV cache shrinks by
    # num_heads/num_kv_heads
    num_kv_heads: Optional[int] = None
    layer_names = ["logits", "pool", "hidden", "embed"]
    # (a part of `lm_objective`'s, the counter it feeds) pairs that
    # `training.record_lm_stats` reads: this objective has no parts
    train_counters = ()

    def lm_objective(self, variables, tokens):
        """-> (loss, parts): what `training.make_lm_train_epoch` trains
        on.  Mean next-token cross-entropy in f32 plus 0.01x whatever the
        modules sow under 'losses' (the MoE load-balance term; dense
        models sow nothing and the sum is 0); no parts."""
        (logits, _), mut = self.apply(variables, tokens, mutable=["losses"])
        # optax's integer-label form is logsumexp minus the gathered
        # logit — unlike an explicit log_softmax it materializes no
        # f32 [B, S, V] tensor (0.5GB at the bench config)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), tokens[:, 1:])
        aux = sum(jnp.sum(v) for v in
                  jax.tree.leaves(mut.get("losses", {})))
        return jnp.mean(ce) + 0.01 * aux, {}

    def lm_controller(self, variables, parts):
        """The variables after a step's controllers: there is no state
        here that a gradient does not own."""
        return variables

    @property
    def kv_heads(self) -> int:
        """K/V head count — the KV-cache head dimension every cache
        allocator (generation, batcher) must use."""
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    # what a paged server sizes its pools from (serving/batcher.py, KINDS
    # OF CACHE): every layer keeps the whole context, as a K and a V pool
    # of the KV heads side by side
    cache_kinds = (("full", None),)

    @property
    def layer_kinds(self):
        return (0,) * self.num_layers

    @property
    def cache_rows(self):
        return ((self.kv_heads * self.head_dim,) * 2,)
    input_dtype = jnp.int32  # token ids (FlaxBundle auto-init dummy dtype)

    @property
    def _dense_cls(self):
        from ..ops.quant import dense_cls

        return dense_cls(self.quant)

    @nn.compact
    def __call__(self, tokens, train: bool = False) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        attn = self.attn_fn if self.attn_fn is not None else default_attn(True)
        if self.pos_emb not in ("learned", "rope"):
            raise ValueError(
                f"pos_emb must be 'learned' or 'rope', got "
                f"{self.pos_emb!r} — anything else would silently build a "
                "position-blind model")
        if self.num_kv_heads is not None and (
                self.num_kv_heads < 1
                or self.num_heads % self.num_kv_heads != 0):
            raise ValueError(
                f"num_kv_heads={self.num_kv_heads} must divide "
                f"num_heads={self.num_heads}")
        taps: Dict[str, jnp.ndarray] = {}
        b, s = tokens.shape
        x = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                     name="tok_embed")(tokens)
        if self.pos_emb == "learned":
            pos = nn.Embed(self.max_len, self.embed_dim, dtype=self.dtype,
                           name="pos_embed")(jnp.arange(s))
            x = x + pos[None]
        taps["embed"] = x
        use_rope = self.pos_emb == "rope"
        for i in range(self.num_layers):
            x = _Block(self.num_heads, self.mlp_ratio, self.dtype, attn,
                       dense_cls=self._dense_cls,
                       num_experts=self.moe_experts,
                       moe_capacity=self.moe_capacity, rope=use_rope,
                       kv_heads=self.num_kv_heads,
                       name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        taps["hidden"] = x
        taps["pool"] = jnp.mean(x, axis=1).astype(jnp.float32)
        logits = self._dense_cls(self.vocab_size, use_bias=False,
                                 dtype=self.dtype,
                                 name="head")(x).astype(jnp.float32)
        taps["logits"] = logits
        return logits, taps

    @nn.compact
    def decode_step(self, token, cache, pos, page_table=None):
        """Block decode: token [B, s] int32 at positions pos..pos+s-1
        attends over the per-layer KV cache (written in place at `pos`);
        s=1 is the classic autoregressive step, s>1 serves speculative
        verification / chunked decode.  Returns (logits [B, s, V] f32,
        new_cache).  Parameter names/shapes are identical to __call__, so
        one set of trained weights serves both paths (models/generation.py
        drives this under lax.scan).

        With `page_table` [B, MP] the per-layer cache tuples are shared
        page POOLS (vLLM-style paged KV; see _Block.__call__) — the
        serving batcher's pay-per-page slot mode."""
        x = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                     name="tok_embed")(token)
        if self.pos_emb == "learned":
            pe = nn.Embed(self.max_len, self.embed_dim, dtype=self.dtype,
                          name="pos_embed")
            if jnp.ndim(pos) == 1:        # slot mode: per-row positions
                x = x + pe(pos[:, None]
                           + jnp.arange(token.shape[1])[None])
            else:
                x = x + pe(jnp.arange(token.shape[1]) + pos)[None]
        new_cache = []
        for i in range(self.num_layers):
            x, layer_cache = _Block(
                self.num_heads, self.mlp_ratio, self.dtype, attn_fn=None,
                dense_cls=self._dense_cls, num_experts=self.moe_experts,
                moe_capacity=self.moe_capacity,
                rope=self.pos_emb == "rope",
                kv_heads=self.num_kv_heads,
                name=f"block{i}")(x, cache=cache[i], pos=pos,
                                  page_table=page_table)
            new_cache.append(layer_cache)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        logits = self._dense_cls(self.vocab_size, use_bias=False,
                                 dtype=self.dtype,
                                 name="head")(x).astype(jnp.float32)
        return logits, tuple(new_cache)


def transformer_lm(vocab_size=1024, embed_dim=128, num_layers=2, num_heads=4,
                   max_len=2048, dtype=jnp.bfloat16, attn_fn=None,
                   quant=False, moe_experts=0, moe_capacity=1.25,
                   pos_emb="learned", num_kv_heads=None, num_classes=None):
    """Builder (zoo registry).  `num_classes` is accepted and ignored so the
    generic builder call sites (get_builder(name)(num_classes=...)) work."""
    return TransformerLM(vocab_size=vocab_size, embed_dim=embed_dim,
                         num_layers=num_layers, num_heads=num_heads,
                         max_len=max_len, dtype=dtype, attn_fn=attn_fn,
                         quant=quant, moe_experts=moe_experts,
                         moe_capacity=moe_capacity, pos_emb=pos_emb,
                         num_kv_heads=num_kv_heads)
