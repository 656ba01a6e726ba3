"""Pallas TPU kernel for the attention hot path.

The dense attention in parallel/ring_attention.full_attention materializes
the [B, H, S, S] score tensor in HBM — at S=4096, bf16, that is 32MB per
(batch, head) of pure bandwidth.  This kernel keeps each query block's
scores VMEM-resident: one HBM read of Q/K/V and one write of O per block,
the flash-attention traffic shape (Liu et al. ring attention's intra-chip
sibling; reference has no analog — its deepest attention is CNTK-era).

Mosaic-friendly formulation (same playbook as pallas_kernels.py):
  - Q/K/V reshaped OUTSIDE the kernel to [B*H, S, D] (no in-kernel
    reshapes); head_dim runs NATIVE at 64-multiples (`_kernel_d`; the
    v5e compiler takes the 64-minor tiles, tests/test_aot_tpu_compile.py
    holds it to that) — padding d=64 up to the lane would double the
    QK^T MACs with zeros and materialize 2x-size q/k/v/o copies around
    every call; other dims pad to the 128 lane.
  - grid = (B*H, S/block_q, S/block_k), K innermost: K/V blocks STREAM
    through VMEM while running max / normalizer / unnormalized output
    live in VMEM scratch across the K steps (online softmax, the true
    flash-attention recurrence) — so VMEM use is O(block_q * block_k),
    independent of S; block_k adapts to the largest block tiling S, so
    any 128-multiple sequence length takes the kernel.
  - scores/softmax in f32; both matmuls via dot_general with f32
    accumulation; causal mask from broadcasted_iota (2D iota is
    Mosaic-legal, 1D is not); the m/l running statistics are stored
    lane-broadcast as [block_q, 128] blocks (a bare [block_q] vector
    is not a legal Mosaic tile).

Training: fused_attention carries a custom VJP whose BACKWARD is the
flash-attention backward as two more Pallas kernels (one accumulates
dK/dV streaming Q blocks, one accumulates dQ streaming K blocks),
recomputing each score block in VMEM from the forward's saved
logsumexp — the dense-XLA backward materialized f32 [B, H, S, S]
score tensors per layer.  Shapes `kernel_ok` declines
run the XLA composition forward and backward.  There is no other
fallback: a shape `kernel_ok` admits and the compiler refuses raises
(under an outer jit, when the outer program compiles).

Off TPU the kernel runs interpret=True (tests/CI); on TPU it compiles to
Mosaic.  tests/test_attention_kernels.py holds the parity suite,
tests/test_aot_tpu_compile.py the compiles for a described v5e, and
chip_smoke.py the run on the chip.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .pallas_kernels import PALLAS_IMAGE_VMEM_BUDGET, _interpret, _pad_up

__all__ = ["fused_attention", "attention_fits_vmem", "kernel_ok"]

_BLOCK_Q = 128
_BLOCK_K = 512
_LANE = 128
_NEG_INF = -1e30  # finite stand-in: -inf arithmetic is fragile on Mosaic


def _pick_block_k(s: int) -> int:
    """Largest K block that tiles s — any 128-multiple S gets a kernel."""
    for blk in (_BLOCK_K, 256, 128):
        if s >= blk and s % blk == 0:
            return blk
    return s  # s < 128: single block (s itself must divide by 8)


def attention_fits_vmem(s: int, d: int, itemsize: int = 2,
                        block_q: int = _BLOCK_Q,
                        block_k: int = _BLOCK_K) -> bool:
    """Per-grid-step VMEM estimate — O(block_q * block_k), NOT O(S):
    K/V blocks stream while the accumulators persist.  Taking the kernel
    path commits callers to the flash BACKWARD too (custom_vjp), whose
    dK/dV kernel stages the most: both estimates must fit."""
    d_p = _pad_up(d, _LANE)
    block_k = _pick_block_k(s) if block_k == _BLOCK_K else min(block_k, s)
    block_q = min(block_q, s)
    fwd = (2 * block_k * d_p * itemsize       # K + V blocks
           + block_q * d_p * itemsize         # Q block
           + 2 * block_q * block_k * 4        # scores + probs (f32)
           + block_q * d_p * 4                # O scratch
           + 2 * block_q * _LANE * 4)         # m / l scratch
    bwd = (2 * block_k * d_p * itemsize       # K + V blocks
           + 2 * block_q * d_p * itemsize     # Q + dO blocks
           + 2 * block_q * _LANE * 4          # lse + delta blocks
           + 3 * block_q * block_k * 4        # p / dp / ds (f32)
           + 2 * block_k * d_p * 4)           # dK + dV accumulators
    return max(fwd, bwd) <= PALLAS_IMAGE_VMEM_BUDGET


def _masked_scores(qb, kb, qi, ki, block_q, block_k, scale, causal,
                   kv_valid=None):
    """Score block sc = scale * Q K^T with the causal and/or KV-padding
    mask applied — THE shared definition for the forward and both
    backward kernels, so mask/scale/_NEG_INF semantics cannot
    desynchronize between them.  `kv_valid` (static) masks key columns
    >= the true sequence length when S was padded up to the block grid:
    zero-padded K rows would otherwise score 0 and steal softmax mass
    from every valid query."""
    sc = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # [bq, bk]
    if causal or kv_valid is not None:
        cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = None
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            mask = (qi * block_q + rows) >= (ki * block_k + cols)
        if kv_valid is not None:
            kv_mask = (ki * block_k + cols) < kv_valid
            mask = kv_mask if mask is None else (mask & kv_mask)
        sc = jnp.where(mask, sc, _NEG_INF)
    return sc


def _dscores(p, dob, vb, dlt, scale):
    """ds = p * (dO V^T - delta) * scale — shared by both backward
    kernels (dp in f32, ds cast at the consuming matmul)."""
    dp = jax.lax.dot_general(
        dob, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bq, bk]
    return p * (dp - dlt) * scale


@partial(jax.jit, static_argnames=("causal", "scale", "kv_valid"))
def _attention_pallas(q, k, v, causal: bool, scale: float,
                      kv_valid=None):
    """q,k,v: [BH, S, D_padded] (D padded to a lane multiple) -> [BH, S,
    D_padded] f32.  `scale` is 1/sqrt(TRUE head dim) — the padded D must
    not leak into the softmax temperature."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    block_q = min(_BLOCK_Q, s)
    block_k = _pick_block_k(s)
    n_k = s // block_k

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc, m_acc, l_acc,
               *, scale):
        ki = pl.program_id(2)
        qi = pl.program_id(1)

        @pl.when(ki == 0)
        def _init():
            o_acc[...] = jnp.zeros_like(o_acc)
            m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
            l_acc[...] = jnp.zeros_like(l_acc)

        # causal: K blocks entirely above the diagonal are pure no-op work
        # (up to ~half the grid at long S) — skip both matmuls for them
        visible = ((qi * block_q + block_q - 1 >= ki * block_k)
                   if causal else (ki >= 0))

        @pl.when(visible)
        def _update():
            qb = q_ref[0]                    # [block_q, D]
            kb = k_ref[0]                    # [block_k, D]
            vb = v_ref[0]
            sc = _masked_scores(qb, kb, qi, ki, block_q, block_k,
                                scale, causal, kv_valid)
            # online softmax: m/l live lane-broadcast in [bq, LANE]
            # scratch.  Read via full-tile load + lane reduction (all
            # lanes hold the same value) — a narrow [:, :1] ref slice is
            # not a safe Mosaic tile access
            m_prev = jnp.max(m_acc[...], axis=-1, keepdims=True)
            l_prev = jnp.max(l_acc[...], axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)                        # [bq, bk] f32
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            o_acc[...] = o_acc[...] * corr + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)
            l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)

        @pl.when(ki == n_k - 1)
        def _finish():
            # fully-masked rows (possible only with non-causal all-pad
            # inputs) keep l=0; guard the divide.  Full-tile read + lane
            # reduction again (lanes are equal by construction).
            l_fin = jnp.max(l_acc[...], axis=-1, keepdims=True)
            o_ref[0] = o_acc[...] / jnp.maximum(l_fin, 1e-20)
            # logsumexp residual for the flash backward: rows the causal
            # mask fully hides never update m (=-inf stand-in) — their
            # lse is meaningless and the backward masks them anyway
            m_fin = jnp.max(m_acc[...], axis=-1, keepdims=True)
            lse = m_fin + jnp.log(jnp.maximum(l_fin, 1e-20))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])

    return pl.pallas_call(
        partial(kernel, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((bh, s, _LANE), jnp.float32)),
        grid=(bh, s // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)


def _xla_attention(q, k, v, causal: bool):
    from ..parallel.ring_attention import full_attention

    return full_attention(q, k, v, causal=causal)


@partial(jax.jit, static_argnames=("causal", "scale", "kv_valid"))
def _attention_bwd_dkdv(q, k, v, do, lse, delta, causal: bool, scale: float,
                        kv_valid=None):
    """dK/dV: grid (BH, n_k, n_q) with Q innermost — each (b, k-block)
    streams every visible Q/dO block, recomputing its score block from
    the saved lse (p = exp(s - lse), exact, no renormalization pass),
    accumulating dV += p^T dO and dK += ds^T Q in VMEM.  All inputs are
    [BH, S, D_pad] except lse/delta [BH, S, LANE] lane-broadcast."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    block_q = min(_BLOCK_Q, s)
    block_k = _pick_block_k(s)
    n_q = s // block_q

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dk_ref, dv_ref, dk_acc, dv_acc, *, scale):
        kj = pl.program_id(1)
        qi = pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        visible = ((qi * block_q + block_q - 1 >= kj * block_k)
                   if causal else (qi >= 0))

        @pl.when(visible)
        def _update():
            qb = q_ref[0]
            kb = k_ref[0]
            vb = v_ref[0]
            dob = do_ref[0]
            lse = jnp.max(lse_ref[0], axis=-1, keepdims=True)   # [bq, 1]
            dlt = jnp.max(dl_ref[0], axis=-1, keepdims=True)    # [bq, 1]
            sc = _masked_scores(qb, kb, qi, kj, block_q, block_k,
                                scale, causal, kv_valid)
            p = jnp.exp(sc - lse)                                # [bq, bk]
            dv_acc[...] += jax.lax.dot_general(
                p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # [bk, D]
            ds = _dscores(p, dob, vb, dlt, scale)
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # [bk, D]

        @pl.when(qi == n_q - 1)
        def _finish():
            dk_ref[0] = dk_acc[...]
            dv_ref[0] = dv_acc[...]

    return pl.pallas_call(
        partial(kernel, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((bh, s, d), jnp.float32)),
        grid=(bh, s // block_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)


@partial(jax.jit, static_argnames=("causal", "scale", "kv_valid"))
def _attention_bwd_dq(q, k, v, do, lse, delta, causal: bool, scale: float,
                      kv_valid=None):
    """dQ: grid (BH, n_q, n_k) with K innermost — the forward's layout,
    accumulating dQ += ds @ K across the streamed K/V blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    block_q = min(_BLOCK_Q, s)
    block_k = _pick_block_k(s)
    n_k = s // block_k

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, dq_acc, *, scale):
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        visible = ((qi * block_q + block_q - 1 >= ki * block_k)
                   if causal else (ki >= 0))

        @pl.when(visible)
        def _update():
            qb = q_ref[0]
            kb = k_ref[0]
            vb = v_ref[0]
            dob = do_ref[0]
            lse = jnp.max(lse_ref[0], axis=-1, keepdims=True)
            dlt = jnp.max(dl_ref[0], axis=-1, keepdims=True)
            sc = _masked_scores(qb, kb, qi, ki, block_q, block_k,
                                scale, causal, kv_valid)
            p = jnp.exp(sc - lse)
            ds = _dscores(p, dob, vb, dlt, scale)
            dq_acc[...] += jax.lax.dot_general(
                ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # [bq, D]

        @pl.when(ki == n_k - 1)
        def _finish():
            dq_ref[0] = dq_acc[...]

    return pl.pallas_call(
        partial(kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        grid=(bh, s // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)


def _padded_len(s: int):
    """Kernel-grid sequence length for s, or None when the kernel should
    decline.  Non-block-multiple lengths (ViT's S=196, ragged text) pad
    up to the 128 grid with `kv_valid` masking — accepted only while the
    padded work stays within 1.5x of the true length, past which the
    masked blocks cost more than XLA dense's score traffic."""
    if s < 8:
        return None
    if s % min(_BLOCK_Q, s) == 0 and s % 8 == 0:
        return s                       # native fit, no padding
    s_p = _pad_up(s, _BLOCK_Q)
    return s_p if 2 * s_p <= 3 * s else None


def kernel_ok(q) -> bool:
    """Public predicate: will fused_attention take the Pallas kernel for
    this (B, S, H, D) array, or fall back to the XLA composition?"""
    b, s, h, d = q.shape
    s_p = _padded_len(s)
    if s_p is None:
        return False
    # lane padding below d=64 (4x+ wasted MXU work and padded HBM copies)
    # makes the kernel a net loss vs XLA dense — keep small heads on XLA
    if d < 64:
        return False
    return attention_fits_vmem(s_p, d, q.dtype.itemsize)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_attention(q, k, v, causal: bool = True):
    """Drop-in for full_attention: (B, S, H, D) -> (B, S, H, D) f32.

    VMEM-resident scores on TPU via Pallas (interpret mode elsewhere).
    Non-block-multiple S (ViT's 196, ragged text) pads up to the 128
    grid with kv_valid masking while the padded work stays within 1.5x
    of the true length (`_padded_len`); beyond that, and for head dim
    < 64 (lane padding wastes the MXU), the XLA composition runs
    instead — `kernel_ok(q)` is the public predicate.  Scale uses the
    TRUE head dim even when D pads to the 128 lane.  Differentiable:
    kernel-path shapes take the flash backward kernels (blockwise
    recompute from the saved logsumexp — matches the XLA gradients to
    MXU precision, ~1e-3 on bf16 passes); shapes `kernel_ok` declines
    keep the exact XLA recompute.
    """
    return _fused_attention_fwd(q, k, v, causal)[0]


def _to_bhsd(x, d_p):
    """[B, S, H, D] -> [B*H, S, D_pad] (the kernels' layout)."""
    b, s, h, d = x.shape
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, d)
    if d_p != d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, d_p - d)))
    return x


def _from_bhsd(x, b, s, h, d):
    return x[..., :d].reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _pad_seq(x, s_p):
    s = x.shape[1]
    if s_p == s:
        return x
    return jnp.pad(x, ((0, 0), (0, s_p - s), (0, 0)))


def _kernel_d(d: int) -> int:
    """Head-dim the kernels run at: 64-multiples are native (64, 128,
    192, ... — all three kernels compile at the 64-minor tiles for a
    v5e in bf16 and f32, tests/test_aot_tpu_compile.py); everything else
    pads up to the 128 lane."""
    return d if d % 64 == 0 else _pad_up(d, _LANE)


def _run_kernel(q, k, v, causal: bool):
    b, s, h, d = q.shape
    d_p = _kernel_d(d)
    s_p = _padded_len(s)
    kv_valid = s if s_p != s else None
    scale = 1.0 / float(d) ** 0.5
    o, lse = _attention_pallas(
        _pad_seq(_to_bhsd(q, d_p), s_p), _pad_seq(_to_bhsd(k, d_p), s_p),
        _pad_seq(_to_bhsd(v, d_p), s_p), causal, scale, kv_valid)
    # keep one lane of the broadcast lse as the backward residual
    return _from_bhsd(o[:, :s], b, s, h, d), lse[:, :s, 0]


def _fused_attention_fwd(q, k, v, causal):
    if kernel_ok(q):
        out, lse = _run_kernel(q, k, v, causal)
        return out, (q, k, v, out, lse)
    # the XLA backward recomputes from q/k/v alone — saving `out` here
    # would keep a dead [B, S, H, D] f32 alive until the backward
    return _xla_attention(q, k, v, causal), (q, k, v, None, None)


def _fused_attention_bwd(causal, res, g):
    q, k, v, out, lse = res
    if lse is None:  # forward ran the XLA composition: exact recompute
        _, vjp = jax.vjp(lambda q, k, v: _xla_attention(q, k, v, causal),
                         q, k, v)
        return vjp(g)
    return _flash_bwd(q, k, v, out, lse, g, causal)


def _flash_bwd(q, k, v, out, lse, g, causal):
    b, s, h, d = q.shape
    d_p, s_p = _kernel_d(d), _padded_len(s)  # the forward's decisions
    kv_valid = s if s_p != s else None
    scale = 1.0 / float(d) ** 0.5
    # delta = rowsum(dO * O) on the TRUE head dim (pad columns are zero).
    # Padded Q rows are inert by construction: their dO rows pad to zero,
    # so every dv/dk contribution they touch is zero; lse/delta pad 0.
    delta = jnp.einsum("bshd,bshd->bhs", g.astype(jnp.float32), out)
    delta = _pad_seq(delta.reshape(b * h, s)[..., None], s_p)
    delta = jnp.broadcast_to(delta, (b * h, s_p, _LANE))
    lse = jnp.broadcast_to(_pad_seq(lse[..., None], s_p),
                           (b * h, s_p, _LANE))
    # matmul-heavy backward runs at the inputs' dtype (bf16 on the MXU)
    # with f32 accumulation, like the forward
    qp, kp, vp = (_pad_seq(_to_bhsd(x, d_p), s_p) for x in (q, k, v))
    dop = _pad_seq(_to_bhsd(g.astype(q.dtype), d_p), s_p)
    dk, dv = _attention_bwd_dkdv(qp, kp, vp, dop, lse, delta, causal,
                                 scale, kv_valid)
    dq = _attention_bwd_dq(qp, kp, vp, dop, lse, delta, causal,
                           scale, kv_valid)
    return (_from_bhsd(dq[:, :s], b, s, h, d).astype(q.dtype),
            _from_bhsd(dk[:, :s], b, s, h, d).astype(k.dtype),
            _from_bhsd(dv[:, :s], b, s, h, d).astype(v.dtype))


fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)


# ---- prefill: causal, grouped heads, optional window (forward only) -------
@partial(jax.jit, static_argnames=("group", "window", "scale"))
def _prefill_attention_pallas(q, k, v, group: int, window, scale: float):
    """q [B*H, S, D], k/v [B*Hkv, S, D] (H = Hkv * group; query head
    b*H + h reads KV head (b*H + h) // group) -> [B*H, S, D] f32.

    The flash forward again, for serving's admission prefill: causal,
    and with `window` (static) only keys in (query - window, query].  The
    K axis of the grid is RELATIVE: step j of query block i reads key
    block first(i) + j, first(i) the block of the oldest key the window
    still shows to the block's first query, so a window layer visits
    (window + block_q) / block_k + 1 key blocks a query block whatever
    the prompt's length, and a full layer all of them (those above the
    diagonal skipped, copy and compute)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    block_q = min(_BLOCK_Q, s)
    block_k = min(256, s) if window is not None else _pick_block_k(s)
    n_kb = s // block_k
    n_rel = n_kb
    if window is not None:
        n_rel = min(n_kb, (window + block_q - 2) // block_k + 2)

    def first(qi):
        if window is None:
            return 0
        return jnp.maximum(qi * block_q - window + 1, 0) // block_k

    def kernel(q_ref, k_ref, v_ref, o_ref, o_acc, m_acc, l_acc):
        qi = pl.program_id(1)
        j = pl.program_id(2)
        kb_i = first(qi) + j

        @pl.when(j == 0)
        def _init():
            o_acc[...] = jnp.zeros_like(o_acc)
            m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
            l_acc[...] = jnp.zeros_like(l_acc)

        @pl.when(kb_i * block_k <= qi * block_q + block_q - 1)
        def _update():
            qb, kb, vb = q_ref[0], k_ref[0], v_ref[0]
            sc = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 0)
            cols = kb_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 1)
            seen = rows >= cols
            if window is not None:
                seen = seen & (cols > rows - window)
            sc = jnp.where(seen, sc, _NEG_INF)
            m_prev = jnp.max(m_acc[...], axis=-1, keepdims=True)
            l_prev = jnp.max(l_acc[...], axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # a row whose every key of this block is masked keeps m at the
            # stand-in: its p must be 0, not exp(0)
            p = jnp.where(seen, jnp.exp(sc - m_new), 0.0)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            o_acc[...] = o_acc[...] * corr + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)
            l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)

        @pl.when(j == n_rel - 1)
        def _finish():
            l_fin = jnp.max(l_acc[...], axis=-1, keepdims=True)
            o_ref[0] = o_acc[...] / jnp.maximum(l_fin, 1e-20)

    def kv_block(b, i, j):
        # blocks above the diagonal park on the diagonal's: no new copy
        diag = (i * block_q + block_q - 1) // block_k
        return (b // group, jnp.minimum(first(i) + j, diag), 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        grid=(bh, s // block_q, n_rel),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, d), kv_block),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)


def _xla_prefill_attention(q, k, v, window):
    """Dense composition of the same: [B, S, H|Hkv, D] -> [B, S, H, D]
    f32."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) / jnp.sqrt(
                        jnp.float32(d))
    rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = rows >= cols
    if window is not None:
        seen = seen & (cols > rows - window)
    p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def prefill_attention_ok(q) -> bool:
    """[B, S, H, D]: lane-wide heads and a sequence the blocks tile."""
    _b, s, _h, d = q.shape
    return d % _LANE == 0 and s % 8 == 0 and (s <= _BLOCK_Q or s % 256 == 0)


def prefill_attention(q, k, v, window=None, kernel: bool = True):
    """Causal attention of a whole prompt with grouped heads and an
    optional window: q [B, S, H, D], k/v [B, S, Hkv, D] -> [B, S, H, D]
    f32.  `kernel` False (or a shape the kernel declines) takes the XLA
    composition."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    if not (kernel and prefill_attention_ok(q)):
        return _xla_prefill_attention(q, k, v, window)
    o = _prefill_attention_pallas(
        _to_bhsd(q, d), _to_bhsd(k, d), _to_bhsd(v, d), group=h // hkv,
        window=window, scale=1.0 / float(d) ** 0.5)
    return _from_bhsd(o, b, s, h, d)
