"""Pallas TPU kernel for paged-KV decode attention.

The XLA paged decode path (models/transformer.py `_Block.__call__`,
page_table branch) gathers every slot's pages into a logical
[B, L, Hkv, D] view per step — correct, but the cache READ touches all
MP pages per slot whether live or not.  This kernel walks the page table
instead (the vLLM paged-attention shape, TPU-style):

  - grid = (B, MP), page index j innermost.  The K/V block specs select
    the PHYSICAL page via the scalar-prefetched page table
    (`PrefetchScalarGridSpec`): block j of slot b is pool page
    table[b, j].  Pages past the slot's live length all map to the
    write-trash page 0, and Mosaic skips the HBM->VMEM copy when
    consecutive iterations map to the same block — so DMA volume scales
    with LIVE pages, not MP.
  - one grid step processes ALL heads of one page: scores/output are
    elementwise multiply + reduce (VPU work, no batched dot_general —
    decode attention is bandwidth-bound, the MXU is irrelevant here),
    masked by the slot position, accumulated across pages with the
    online-softmax recurrence in VMEM scratch (same shape as
    attention_kernels.py).

Exactness: parity vs the XLA gather path is enforced in
tests/test_paged_attention.py (interpret mode on CPU; the on-chip Mosaic
compile+parity rides `mfu_sweep --decode`'s paged case).  Callers route
through `paged_decode_attention`, which owns the dispatch: the
conservative shape/VMEM gate (`paged_kernel_ok`) keeps ineligible
configs — GQA pools, odd head dims, oversized pages — on the XLA
composition.  If a gated-in shape still trips Mosaic on real hardware
(the gate is an estimate), the failure surfaces at the serving step's
first compile; `MMLSPARK_NO_PAGED_KERNEL=1` forces the gather path
without a code change.  Scope of that switch: the env var is read at
TRACE time, so it must be set BEFORE the serving process compiles its
first paged step — flipping it in an already-running server does
nothing for programs XLA has already compiled (restart the process, or
clear the jit caches with `jax.clear_caches()` and let the next step
retrace).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .pallas_kernels import PALLAS_IMAGE_VMEM_BUDGET, _interpret

__all__ = ["paged_decode_attention", "paged_decode_attention_int8",
           "paged_kernel_ok"]

_NEG_INF = -1e30
_LANE = 128


def paged_kernel_ok(q, k_pool) -> bool:
    """Will the Pallas page-walk kernel take this shape?  q [B, H, D],
    k_pool [NP, page, H, D].  Conservative: lane-friendly head dim,
    sublane-friendly page size, MHA pools only (GQA expands head count
    between q and pool — the XLA gather path serves it), and the
    per-step working set must fit the VMEM budget (an oversized page
    config must route to the gather, not die in Mosaic)."""
    import os

    if os.environ.get("MMLSPARK_NO_PAGED_KERNEL"):
        return False
    b, h, d = q.shape
    np_, page, hk, dk = k_pool.shape
    if (hk, dk) != (h, d):
        return False
    if not (d % 64 == 0 and page % 8 == 0 and page >= 8):
        return False
    item = k_pool.dtype.itemsize
    staged = (2 * page * h * d * item     # K + V page blocks (DMA)
              # f32 staging is charged regardless of pool dtype: the int8
              # kernel materializes f32 dequant copies of both blocks, so
              # its working set is NOT smaller than f32's — an int8 gate
              # looser than the f32 gate would promise Mosaic shapes it
              # rejects
              + 4 * page * h * d * 4      # dequant copies + mul intermediates
              + 2 * h * d * 4             # q block + o scratch (f32)
              + 4 * page * h * 4          # scores/probs + scale blocks
              + 2 * h * _LANE * 4)        # m / l scratch
    return staged <= PALLAS_IMAGE_VMEM_BUDGET


@partial(jax.jit, static_argnames=())
def _paged_pallas(q, k_pool, v_pool, page_table, pos):
    """q [B, H, D]; pools [NP, page, H, D]; table [B, MP] i32; pos [B]
    i32 -> [B, H, D] f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    np_, page, _, _ = k_pool.shape
    mp = page_table.shape[1]
    scale = 1.0 / float(d) ** 0.5

    def kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
               o_acc, m_acc, l_acc):
        bi = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            o_acc[...] = jnp.zeros_like(o_acc)
            m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
            l_acc[...] = jnp.zeros_like(l_acc)

        p_b = pos_ref[bi]
        # pages whose first position is past the slot's write position
        # hold nothing visible — skip their compute entirely (their DMA
        # was already skipped: the index_map parks them on page 0)
        @pl.when(j * page <= p_b)
        def _update():
            qb = q_ref[0]                       # [H, D]
            kb = k_ref[0]                       # [page, H, D]
            vb = v_ref[0]
            # scores[p, h] = sum_d k[p,h,d] * q[h,d] — VPU reduce, no
            # batched dot (decode reads dominate; MXU is irrelevant)
            sc = jnp.sum(kb.astype(jnp.float32) *
                         qb[None].astype(jnp.float32), axis=-1) * scale
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            sc = jnp.where(j * page + rows <= p_b, sc, _NEG_INF)
            # online softmax over the page axis, stats per head kept
            # lane-broadcast in [H, LANE] scratch (axis-0 stats of the
            # [page, H] tile, swapped into head-major [H, 1])
            m_prev = jnp.max(m_acc[...], axis=-1, keepdims=True)  # [H, 1]
            l_prev = jnp.max(l_acc[...], axis=-1, keepdims=True)
            m_cur = jnp.swapaxes(jnp.max(sc, axis=0, keepdims=True), 0, 1)
            m_new = jnp.maximum(m_prev, m_cur)                    # [H, 1]
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - jnp.swapaxes(m_new, 0, 1))           # [page, H]
            l_new = l_prev * corr + jnp.swapaxes(
                jnp.sum(p, axis=0, keepdims=True), 0, 1)
            o_acc[...] = (o_acc[...] * corr +
                          jnp.sum(p[:, :, None] * vb.astype(jnp.float32),
                                  axis=0))
            m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)
            l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)

        @pl.when(j == mp - 1)
        def _finish():
            l_fin = jnp.max(l_acc[...], axis=-1, keepdims=True)
            o_ref[0] = o_acc[...] / jnp.maximum(l_fin, 1e-20)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # page_table (flat) + pos
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, j, tbl, pos: (bi, 0, 0)),
            pl.BlockSpec((1, page, h, d),
                         lambda bi, j, tbl, pos: (tbl[bi * mp + j], 0, 0, 0)),
            pl.BlockSpec((1, page, h, d),
                         lambda bi, j, tbl, pos: (tbl[bi * mp + j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, j, tbl, pos: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, _LANE), jnp.float32),
            pltpu.VMEM((h, _LANE), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        grid_spec=grid_spec,
        interpret=_interpret(),
    )(page_table.reshape(-1), pos, q, k_pool, v_pool)


@partial(jax.jit, static_argnames=())
def _paged_pallas_int8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                       page_table, pos):
    """int8 variant: pools are int8 [NP, page, H, D] with per-(pos, head)
    f32 scales [NP, page, H] (ops/quant.quantize_kv_row rows).  The
    dequant multiplies ride the tiny [page, H] score/prob tensors —
    exactly `_cache_attention`'s quant factoring — so the HBM read stays
    1/4 of f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    np_, page, _, _ = kq_pool.shape
    mp = page_table.shape[1]
    scale = 1.0 / float(d) ** 0.5

    def kernel(tbl_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
               o_ref, o_acc, m_acc, l_acc):
        bi = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            o_acc[...] = jnp.zeros_like(o_acc)
            m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
            l_acc[...] = jnp.zeros_like(l_acc)

        p_b = pos_ref[bi]

        @pl.when(j * page <= p_b)
        def _update():
            qb = q_ref[0].astype(jnp.float32)    # [H, D]
            kb = kq_ref[0].astype(jnp.float32)   # [page, H, D] int8->f32
            vb = vq_ref[0].astype(jnp.float32)
            ksb = ks_ref[0]                      # [page, H] f32 scales
            vsb = vs_ref[0]
            sc = jnp.sum(kb * qb[None], axis=-1) * ksb * scale
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            sc = jnp.where(j * page + rows <= p_b, sc, _NEG_INF)
            m_prev = jnp.max(m_acc[...], axis=-1, keepdims=True)
            l_prev = jnp.max(l_acc[...], axis=-1, keepdims=True)
            m_cur = jnp.swapaxes(jnp.max(sc, axis=0, keepdims=True), 0, 1)
            m_new = jnp.maximum(m_prev, m_cur)
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - jnp.swapaxes(m_new, 0, 1))          # [page, H]
            l_new = l_prev * corr + jnp.swapaxes(
                jnp.sum(p, axis=0, keepdims=True), 0, 1)
            o_acc[...] = (o_acc[...] * corr +
                          jnp.sum((p * vsb)[:, :, None] * vb, axis=0))
            m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)
            l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)

        @pl.when(j == mp - 1)
        def _finish():
            l_fin = jnp.max(l_acc[...], axis=-1, keepdims=True)
            o_ref[0] = o_acc[...] / jnp.maximum(l_fin, 1e-20)

    page_spec = pl.BlockSpec(
        (1, page, h, d), lambda bi, j, tbl, pos: (tbl[bi * mp + j], 0, 0, 0))
    scale_spec = pl.BlockSpec(
        (1, page, h), lambda bi, j, tbl, pos: (tbl[bi * mp + j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, j, tbl, pos: (bi, 0, 0)),
            page_spec, scale_spec, page_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, j, tbl, pos: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, _LANE), jnp.float32),
            pltpu.VMEM((h, _LANE), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        grid_spec=grid_spec,
        interpret=_interpret(),
    )(page_table.reshape(-1), pos, q, kq_pool, ks_pool, vq_pool, vs_pool)


def _xla_paged_int8(q, kq_pool, ks_pool, vq_pool, vs_pool, page_table, pos):
    """Gather fallback with the same quant factoring as _cache_attention."""
    b, h, d = q.shape
    np_, page, hk, _ = kq_pool.shape
    mp = page_table.shape[1]
    L = mp * page
    kq = kq_pool[page_table].reshape(b, L, hk, d)
    vq = vq_pool[page_table].reshape(b, L, hk, d)
    ks = ks_pool[page_table].reshape(b, L, hk)
    vs = vs_pool[page_table].reshape(b, L, hk)
    if hk != h:
        kq = jnp.repeat(kq, h // hk, axis=2)
        vq = jnp.repeat(vq, h // hk, axis=2)
        ks = jnp.repeat(ks, h // hk, axis=2)
        vs = jnp.repeat(vs, h // hk, axis=2)
    sc = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                    kq.astype(jnp.float32))
    sc = sc * ks.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
    valid = jnp.arange(L)[None, None, :] <= pos[:, None, None]
    sc = jnp.where(valid, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1) * vs.transpose(0, 2, 1)
    return jnp.einsum("bhk,bkhd->bhd", p, vq.astype(jnp.float32))


def paged_decode_attention_int8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                                page_table, pos):
    """int8 paged decode attention (the 4-tuple cache form): page-walk
    kernel when eligible, quant-factored XLA gather otherwise."""
    if paged_kernel_ok(q, kq_pool):
        return _paged_pallas_int8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                                  page_table.astype(jnp.int32),
                                  pos.astype(jnp.int32))
    return _xla_paged_int8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                           page_table, pos)


def _xla_paged(q, k_pool, v_pool, page_table, pos):
    """Reference semantics: gather pages -> masked softmax attention.
    Mirrors models/transformer._cache_attention for the paged branch.
    GQA pools (hk < h) expand to the query head count after the gather."""
    b, h, d = q.shape
    np_, page, hk, _ = k_pool.shape
    mp = page_table.shape[1]
    k_log = k_pool[page_table].reshape(b, mp * page, hk, d)
    v_log = v_pool[page_table].reshape(b, mp * page, hk, d)
    if hk != h:
        k_log = jnp.repeat(k_log, h // hk, axis=2)
        v_log = jnp.repeat(v_log, h // hk, axis=2)
    sc = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                    k_log.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    valid = jnp.arange(mp * page)[None, None, :] <= pos[:, None, None]
    sc = jnp.where(valid, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v_log.astype(jnp.float32))


def paged_decode_attention(q, k_pool, v_pool, page_table, pos):
    """Single-token paged decode attention: q [B, H, D] over page pools
    [NP, page, H, D] addressed by table [B, MP] at per-slot positions
    `pos` [B].  Pallas page-walk kernel when the shape allows, XLA
    gather otherwise — identical numerics either way."""
    if paged_kernel_ok(q, k_pool):
        return _paged_pallas(q, k_pool, v_pool,
                             page_table.astype(jnp.int32),
                             pos.astype(jnp.int32))
    return _xla_paged(q, k_pool, v_pool, page_table, pos)
