"""Sequence parallelism: ring attention + Ulysses all-to-all attention.

The reference has NO sequence parallelism (SURVEY §2.10: "Not present in
reference" — its longest-sequence handling is CNTK dynamic axes); this module
is the TPU-first upgrade that makes long-context first-class, following the
blockwise-ring construction (Liu et al., Ring Attention) and the
DeepSpeed-Ulysses head-scatter construction, both expressed as XLA
collectives over the mesh:

- ring_attention: K/V blocks rotate around the ICI ring via `ppermute` while
  each device accumulates its queries' attention with a numerically-stable
  online softmax — memory O(S/n) per device, compute fully overlapped.
- ulysses_attention: `all_to_all` reshards (seq-sharded -> head-sharded),
  runs dense per-head attention, and reshards back — cheaper at moderate S,
  requires heads % n == 0.

Both are exact: they match full attention to float tolerance.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import shard_map

__all__ = ["full_attention", "ring_attention", "ulysses_attention"]


def full_attention(q, k, v, causal: bool = False):
    """Reference dense attention.  q,k,v: (B, S, H, D) -> (B, S, H, D) f32.

    MXU-friendly mixed precision: the two matmuls run at the INPUT dtype
    (bf16 inputs hit the systolic array at full rate) with f32
    accumulation (`preferred_element_type`); softmax statistics stay f32.
    f32 inputs are bit-identical to the previous formulation.
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _block_accumulate(q, k_blk, v_blk, o, m, l, q_off, k_off, causal: bool):
    """Online-softmax accumulation of one K/V block into (o, m, l).

    q: (B, Sq, H, D) local queries at global offset q_off;
    k_blk/v_blk: (B, Sk, H, D) at global offset k_off.
    o: (B, Sq, H, D) unnormalized; m,l: (B, H, Sq) running max / normalizer.
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    if causal:
        qpos = q_off + jnp.arange(q.shape[1])
        kpos = k_off + jnp.arange(k_blk.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)                      # (B, H, Sq)
    m_new = jnp.maximum(m, m_blk)
    # fully-masked blocks produce -inf maxima; keep exp() finite
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(s), s - m_safe[..., None], -jnp.inf))
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32,
    )
    return o_new, m_new, l_new


def _resolve_axis(mesh: Mesh, axis: Optional[str]) -> str:
    """Default to the mesh's dedicated 'seq' axis when it is populated
    (mesh.py reserves it for sequence parallelism); else fall back to
    'data' so an all-data mesh still works."""
    if axis is not None:
        return axis
    if mesh.shape.get("seq", 1) > 1:
        return "seq"
    return "data"


def _ring_driver(q, k, v, mesh: Mesh, axis: str, accumulate):
    """THE ring protocol, shared by the dense and flash paths: K/V blocks
    rotate via ppermute for n-1 scan steps plus one unscanned final
    block (no wasted last rotation); `accumulate(q_loc, k_blk, v_blk,
    o, m, l, q_off, k_off)` folds one held block into the online-softmax
    carry.  One copy of the offset/rotation math means a fix here fixes
    both paths."""
    n = mesh.shape[axis]
    seq_spec = P(None, axis, None, None)

    @partial(
        shard_map, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        check_vma=False,
    )
    def ring(q_loc, k_loc, v_loc):
        idx = jax.lax.axis_index(axis)
        s_loc = q_loc.shape[1]
        q_off = idx * s_loc
        o = jnp.zeros(q_loc.shape, jnp.float32)
        m = jnp.full(
            (q_loc.shape[0], q_loc.shape[2], s_loc), -jnp.inf, jnp.float32
        )
        l = jnp.zeros((q_loc.shape[0], q_loc.shape[2], s_loc), jnp.float32)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, r):
            o, m, l, k_blk, v_blk = carry
            # k/v block currently held came from device (idx - r) mod n
            k_off = ((idx - r) % n) * s_loc
            o, m, l = accumulate(q_loc, k_blk, v_blk, o, m, l, q_off, k_off)
            # rotate: send our block to the next device in the ring
            k_nxt = jax.lax.ppermute(k_blk, axis, perm)
            v_nxt = jax.lax.ppermute(v_blk, axis, perm)
            return (o, m, l, k_nxt, v_nxt), None

        (o, m, l, k_last, v_last), _ = jax.lax.scan(
            step, (o, m, l, k_loc, v_loc), jnp.arange(n - 1)
        )
        o, m, l = accumulate(q_loc, k_last, v_last, o, m, l, q_off,
                             ((idx - (n - 1)) % n) * s_loc)
        return o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]

    return ring(q, k, v)


def _ring_dense(q, k, v, mesh: Mesh, axis: str, causal: bool):
    """The dense-block ring: per-step [Sq, Sk] score blocks in XLA."""

    def accumulate(q_loc, k_blk, v_blk, o, m, l, q_off, k_off):
        return _block_accumulate(q_loc, k_blk, v_blk, o, m, l,
                                 q_off, k_off, causal)

    return _ring_driver(q, k, v, mesh, axis, accumulate)


def _merge_normalized(o, m, l, o_b, lse_b):
    """Fold one NORMALIZED attention block (o_b, lse_b) into the running
    (o, m, l) online-softmax carry.  A normalized block is a weighted
    value with scalar log-weight lse_b per row — the same (reference,
    weight, weighted-values) algebra _block_accumulate maintains, so
    dense and flash steps can mix freely."""
    m_new = jnp.maximum(m, lse_b)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    w = jnp.where(jnp.isfinite(lse_b), jnp.exp(lse_b - m_safe), 0.0)
    o_new = (o * corr.transpose(0, 2, 1)[..., None]
             + o_b * w.transpose(0, 2, 1)[..., None])
    return o_new, m_new, l * corr + w


def _ring_flash_fwd(q, k, v, mesh: Mesh, axis: str, causal: bool):
    """Ring forward with each block's attention in the Pallas flash
    kernel (VMEM-resident scores; the kernel's lse output is exactly the
    per-block merge statistic) — Liu et al.'s construction with the
    intra-block part on the MXU instead of dense XLA.  Causality between
    BLOCKS is static per relation (behind/diagonal/ahead) but the
    relation itself depends on the device index, so the three cases ride
    lax.cond."""
    from ..ops.attention_kernels import _run_kernel

    def accumulate(q_loc, k_blk, v_blk, o, m, l, q_off, k_off):
        b, s_loc, h, _ = q_loc.shape

        def run(blk_causal):
            o_b, lse = _run_kernel(q_loc, k_blk, v_blk, blk_causal)
            # the kernel writes q's dtype; blocks merge in f32
            return o_b.astype(jnp.float32), lse.reshape(b, h, s_loc)

        def skipped():
            return (jnp.zeros(q_loc.shape, jnp.float32),
                    jnp.full((b, h, s_loc), -jnp.inf, jnp.float32))

        if not causal:
            o_b, lse = run(False)
        else:
            # k block strictly behind the queries -> fully visible;
            # same offset -> the kernel's own causal mask IS the global
            # mask (blocks are equal-sized and aligned); ahead -> skip
            o_b, lse = jax.lax.cond(
                k_off < q_off, lambda: run(False),
                lambda: jax.lax.cond(k_off == q_off,
                                     lambda: run(True), skipped))
        return _merge_normalized(o, m, l, o_b, lse)

    return _ring_driver(q, k, v, mesh, axis, accumulate)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, mesh, axis, causal):
    """Flash-forward ring with the dense-ring recompute as backward —
    forward traffic drops to the flash shape while gradients stay the
    exact dense-block autodiff (same containment stance as the fused
    kernel took before its flash backward landed)."""
    return _ring_flash_fwd(q, k, v, mesh, axis, causal)


def _ring_flash_f(q, k, v, mesh, axis, causal):
    return _ring_flash_fwd(q, k, v, mesh, axis, causal), (q, k, v)


def _ring_flash_b(mesh, axis, causal, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: _ring_dense(q, k, v, mesh, axis, causal), q, k, v)
    return vjp(g)


_ring_flash.defvjp(_ring_flash_f, _ring_flash_b)


def ring_attention(q, k, v, mesh: Mesh, axis: Optional[str] = None,
                   causal: bool = False):
    """Exact attention with sequence sharded over `axis` (default: the
    mesh's 'seq' axis if populated, else 'data').

    q,k,v: (B, S, H, D) GLOBAL arrays (or already sharded); S must divide by
    the axis size.  Returns (B, S, H, D) with the same sharding.

    When the LOCAL block shape can take the Pallas kernel, each ring
    step's intra-block attention runs VMEM-resident (flash) and blocks
    merge by their logsumexp; otherwise the dense-block path runs.  Both
    are exact vs full attention (tests assert it).
    """
    from ..ops.attention_kernels import kernel_ok

    axis = _resolve_axis(mesh, axis)
    n = mesh.shape[axis]
    blk = q.shape[1] // n
    local = jax.ShapeDtypeStruct((q.shape[0], blk, q.shape[2], q.shape[3]),
                                 q.dtype)
    if kernel_ok(local):
        return _ring_flash(q, k, v, mesh, axis, causal)
    return _ring_dense(q, k, v, mesh, axis, causal)


def ulysses_attention(q, k, v, mesh: Mesh, axis: Optional[str] = None,
                      causal: bool = False):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses construction).

    Heads must divide by the axis size: reshard (S/n, H) -> (S, H/n), run
    dense attention on full sequences per head shard, reshard back.
    """
    axis = _resolve_axis(mesh, axis)
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(f"heads {q.shape[2]} not divisible by axis size {n}")
    seq_spec = P(None, axis, None, None)

    @partial(
        shard_map, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        check_vma=False,
    )
    def ulysses(q_loc, k_loc, v_loc):
        def scatter_heads(x):
            # (B, S/n, H, D) -> (B, S, H/n, D)
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        def gather_seq(x):
            # (B, S, H/n, D) -> (B, S/n, H, D)
            return jax.lax.all_to_all(
                x, axis, split_axis=1, concat_axis=2, tiled=True
            )

        qg, kg, vg = scatter_heads(q_loc), scatter_heads(k_loc), scatter_heads(v_loc)
        # the per-device inner attention is DENSE over the full sequence —
        # exactly the shape the Pallas flash kernel accelerates; it falls
        # back to the XLA composition for shapes it can't take, so this
        # composes sequence parallelism with the VMEM-resident kernel
        from ..ops.attention_kernels import fused_attention

        og = fused_attention(qg, kg, vg, causal)
        return gather_seq(og)

    return ulysses(q, k, v)
