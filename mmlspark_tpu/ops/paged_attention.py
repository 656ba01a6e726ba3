"""Pallas TPU kernel for paged-KV decode attention.

Page pools are FLAT: [NP, page, Hkv*D] (int8 pools the same, with f32
scale pools [NP, page, Hkv]).  That is the one shape every program that
touches a pool consumes in the layout the compiler gives the parameter
by default (row-major, unpadded: `{2,1,0:T(8,128)(2,1)}` for bf16 on a
v5e) — the decode step's row scatter, the admission's page scatter and
this kernel's page blocks — so a donated pool is updated in place and
never relaid.  A [NP, page, H, D] pool with D=64 gets a compact
parameter layout (page index minor-most) that neither the scatter nor
Mosaic reads: every layer of every step then copied both pools to
row-major and back (PERF.md, PR 25).

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
  - one grid step processes ALL heads of one (1, page, H*D) block.  The
    heads lie side by side on the lane axis, so the per-head reductions
    are products with the [H*D, H] head indicator (`_head_indicator`):
    scores = (k * q) @ seg, and the probabilities (with the running
    rescale as extra rows) go back to the lanes through seg.T, all at
    `precision=HIGHEST` (f32 accuracy).  The MXU is not what this costs:
    at 32 slots x 16 pages of [64, 1024] bf16 a call takes 0.2 ms on a
    v5e whatever the products' precision, about 0.4 us a grid step (my
    chip run, PR 25).  Masked by the slot position, accumulated across
    pages with the online-softmax recurrence in VMEM scratch (m/l
    [1, H], o [1, H*D]).

Exactness: parity vs the XLA gather path is enforced in
tests/test_paged_attention.py (interpret mode on CPU; the Mosaic
compiles for a described v5e are tests/test_aot_tpu_compile.py's).
Callers route through `paged_decode_attention`, which owns the
dispatch: the conservative shape/VMEM gate (`paged_kernel_ok`) keeps
ineligible configs — GQA pools, lane-unfriendly widths, oversized pages
— on the XLA composition.  If a gated-in shape still trips Mosaic on
real hardware (the gate is an estimate), the failure surfaces at the
serving step's first compile; `MMLSPARK_NO_PAGED_KERNEL=1` forces the
gather path without a code change.  Scope of that switch: the env var is
read at TRACE time, so it must be set BEFORE the serving process
compiles its first paged step — flipping it in an already-running
server does nothing for programs XLA has already compiled (restart the
process, or clear the jit caches with `jax.clear_caches()` and let the
next step retrace).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .pallas_kernels import PALLAS_IMAGE_VMEM_BUDGET, _interpret

__all__ = ["paged_decode_attention", "paged_decode_attention_int8",
           "paged_kernel_ok", "paged_mla_attention"]

_NEG_INF = -1e30
_LANE = 128
_SUBLANE = 8


def paged_kernel_ok(q, k_pool) -> bool:
    """Will a Pallas page-walk kernel take this shape?  q [B, H, D],
    k_pool [NP, page, Hkv*D].  Conservative: MHA pools (`_page_walk`) or
    grouped-query pools whose head size is a lane multiple
    (`_page_walk_gqa`), a lane-aligned flat width, a page of whole sublane tiles (8 rows: the
    described v5e compiles f32, bf16 and int8 blocks alike from there),
    and the per-step working set must fit the VMEM budget (an oversized
    page config must route to the gather, not die in Mosaic)."""
    import os

    if os.environ.get("MMLSPARK_NO_PAGED_KERNEL"):
        return False
    b, h, d = q.shape
    _, page, hd = k_pool.shape
    item = k_pool.dtype.itemsize
    if page % _SUBLANE:
        return False
    if hd != h * d:
        # grouped-query pools go to `_page_walk_gqa`: whole query groups
        # on KV heads that are lane tiles of their own; its blocks are a
        # page of K and V and a [Hkv, G, D] query, far inside the budget
        return (d % _LANE == 0 and hd % d == 0 and h % (hd // d) == 0
                and page * hd * item * 4 <= PALLAS_IMAGE_VMEM_BUDGET)
    if hd % _LANE:
        return False
    h_pad = -(-h // _LANE) * _LANE      # [page, H] tiles pad H to the lanes
    staged = (4 * page * hd * item      # K + V page blocks, double-buffered
              # f32 staging is charged regardless of pool dtype: bf16 and
              # int8 blocks are widened to f32 before any arithmetic, so
              # their working set is NOT smaller than f32's
              + 6 * page * hd * 4       # k, v, k*q, probs on the lanes, p*v
                                        # (f32) + the products' operands
              + 2 * hd * h_pad * 4      # seg [H*D, H] and seg.T
              + 6 * (page + _SUBLANE) * h_pad * 4   # scores/probs/scales
              + 4 * hd * 4)             # q, o blocks + o scratch
    return staged <= PALLAS_IMAGE_VMEM_BUDGET


def _head_indicator(hd: int, h: int, lane_axis: int):
    """seg [H*D, H] (lane_axis 0) or seg.T [H, H*D] (lane_axis 1): 1.0
    where the flat lane belongs to the head."""
    shape = (hd, h) if lane_axis == 0 else (h, hd)
    d = hd // h
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, lane_axis)
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - lane_axis)
    return ((lane >= head * d) & (lane < (head + 1) * d)).astype(
        jnp.float32)


def _seg_dot(x, seg):
    """x @ seg (or seg.T) at f32 accuracy: the per-head sum over the
    lanes, or the per-head value spread back over them."""
    return jnp.dot(x, seg, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _page_walk(q, k_pool, v_pool, k_scale, v_scale, page_table, pos):
    """The page walk over flat pools.  q [B, H, D]; pools [NP, page,
    H*D]; scales [NP, page, H] f32 for int8 pools, else None; table
    [B, MP] i32; pos [B] i32 -> [B, H, D] f32.  The int8 dequant
    multiplies ride the tiny [page, H] score/prob tensors — exactly
    `_cache_attention`'s quant factoring — so the HBM read stays 1/4 of
    f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    _, page, hd = k_pool.shape
    mp = page_table.shape[1]
    scale = 1.0 / float(d) ** 0.5
    quant = k_scale is not None

    def kernel(tbl_ref, pos_ref, q_ref, *refs):
        if quant:
            k_ref, ks_ref, v_ref, vs_ref, o_ref, o_acc, m_acc, l_acc = refs
        else:
            k_ref, v_ref, o_ref, o_acc, m_acc, l_acc = refs
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
            qb = q_ref[0].astype(jnp.float32)       # [1, H*D]
            kb = k_ref[0].astype(jnp.float32)       # [page, H*D]
            vb = v_ref[0].astype(jnp.float32)
            # scores[p, h] = sum over head h's lanes of k[p, :] * q
            sc = _seg_dot(kb * qb, _head_indicator(hd, h, 0)) * scale
            if quant:
                sc = sc * ks_ref[0]                 # [page, H] f32 scales
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            sc = jnp.where(j * page + rows <= p_b, sc, _NEG_INF)
            # online softmax over the page axis, stats per head [1, H]
            m_prev = m_acc[...]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)                 # [page, H]
            l_acc[...] = l_acc[...] * corr + jnp.sum(p, axis=0,
                                                     keepdims=True)
            m_acc[...] = m_new
            if quant:
                p = p * vs_ref[0]
            # probabilities and the rescale go back to the lanes in ONE
            # product: corr rides as a sublane tile of extra rows
            full = _seg_dot(
                jnp.concatenate(
                    [p, jnp.broadcast_to(corr, (_SUBLANE, h))], axis=0),
                _head_indicator(hd, h, 1))          # [page + 8, H*D]
            o_acc[...] = (o_acc[...] * full[page:page + 1] +
                          jnp.sum(full[:page] * vb, axis=0, keepdims=True))

        @pl.when(j == mp - 1)
        def _finish():
            l_full = _seg_dot(jnp.broadcast_to(l_acc[...], (_SUBLANE, h)),
                              _head_indicator(hd, h, 1))[:1]
            o_ref[0] = o_acc[...] / jnp.maximum(l_full, 1e-20)

    row_spec = pl.BlockSpec((1, 1, hd), lambda bi, j, tbl, pos: (bi, 0, 0))
    page_spec = pl.BlockSpec(
        (1, page, hd), lambda bi, j, tbl, pos: (tbl[bi * mp + j], 0, 0))
    scale_spec = pl.BlockSpec(
        (1, page, h), lambda bi, j, tbl, pos: (tbl[bi * mp + j], 0, 0))
    if quant:
        in_specs = [row_spec, page_spec, scale_spec, page_spec, scale_spec]
        pools = (k_pool, k_scale, v_pool, v_scale)
    else:
        in_specs = [row_spec, page_spec, page_spec]
        pools = (k_pool, v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # page_table (flat) + pos
        grid=(b, mp),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), jnp.float32),
        grid_spec=grid_spec,
        interpret=_interpret(),
    )(page_table.reshape(-1), pos, q.reshape(b, 1, hd), *pools)
    return out.reshape(b, h, d)


# The jitted wrappers' names are the device events' names: the
# benchmark's `paged_attn_ms` finds the kernel by `^_paged_pallas`.
@jax.jit
def _paged_pallas(q, k_pool, v_pool, page_table, pos):
    """q [B, H, D]; pools [NP, page, H*D]; table [B, MP] i32; pos [B]
    i32 -> [B, H, D] f32."""
    return _page_walk(q, k_pool, v_pool, None, None, page_table, pos)


@jax.jit
def _paged_pallas_int8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                       page_table, pos):
    """int8 variant: pools are int8 [NP, page, H*D] with per-(pos, head)
    f32 scales [NP, page, H] (ops/quant.quantize_kv_row rows)."""
    return _page_walk(q, kq_pool, vq_pool, ks_pool, vs_pool, page_table,
                      pos)


def _ring_first(p_b, window, page):
    """First logical page a window layer's walk visits at write position
    p_b: the page of the oldest key still inside the window."""
    return jnp.maximum(p_b - window + 1, 0) // page


def _page_walk_gqa(q, k_pool, v_pool, page_table, pos, window=None):
    """The page walk over grouped-query pools.  q [B, H, D]; pools [NP,
    page, Hkv*D] with D a lane multiple; table [B, MP] i32; pos [B] i32
    -> [B, H, D] f32.  Query head h reads KV head h // (H / Hkv).

    Each KV head is a lane tile of its own, so a grid step slices the
    page block per KV head and runs two small MXU products for the
    head's whole query group ([G, D] x [D, page], [G, page] x [page, D];
    bf16 operands, f32 accumulation and softmax statistics), the group
    padded to a sublane tile outside the kernel.

    `window` (static): the table is a RING of MP = window / page + 1
    entries, logical page lp living at entry lp % MP (serving/batcher.py
    recycles a slot's window pages that way).  The walk then starts at
    the page of the oldest key inside the window and visits MP pages,
    not the context's; keys at or before pos - window are masked."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    _, page, hd = k_pool.shape
    hkv = hd // d
    g = h // hkv
    gp = -(-g // _SUBLANE) * _SUBLANE
    if k_pool.dtype == jnp.bfloat16:
        gp = -(-g // 16) * 16
    mp = page_table.shape[1]
    scale = 1.0 / float(d) ** 0.5
    qg = jnp.zeros((b, hkv, gp, d), k_pool.dtype).at[:, :, :g].set(
        q.reshape(b, hkv, g, d).astype(k_pool.dtype))

    def logical(j, p_b):
        return j if window is None else _ring_first(p_b, window, page) + j

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
        lp = logical(j, p_b)

        @pl.when(lp * page <= p_b)
        def _update():
            cols = lp * page + jax.lax.broadcasted_iota(
                jnp.int32, (gp, page), 1)
            seen = cols <= p_b
            if window is not None:
                seen = seen & (cols > p_b - window)
            for kv in range(hkv):
                lanes = slice(kv * d, (kv + 1) * d)
                qb = q_ref[0, kv]                     # [Gp, D]
                kb = k_ref[0, :, lanes]               # [page, D]
                vb = v_ref[0, :, lanes]
                sc = jax.lax.dot_general(
                    qb, kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                sc = jnp.where(seen, sc, _NEG_INF)    # [Gp, page]
                m_prev = jnp.max(m_acc[kv], axis=-1, keepdims=True)
                l_prev = jnp.max(l_acc[kv], axis=-1, keepdims=True)
                m_new = jnp.maximum(
                    m_prev, jnp.max(sc, axis=-1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(sc - m_new)
                l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
                o_acc[kv] = o_acc[kv] * corr + jax.lax.dot_general(
                    p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_acc[kv] = jnp.broadcast_to(m_new, (gp, _LANE))
                l_acc[kv] = jnp.broadcast_to(l_new, (gp, _LANE))

        @pl.when(j == mp - 1)
        def _finish():
            for kv in range(hkv):
                l_fin = jnp.max(l_acc[kv], axis=-1, keepdims=True)
                o_ref[0, kv] = o_acc[kv] / jnp.maximum(l_fin, 1e-20)

    def page_of(bi, j, tbl, pos):
        # pages past the write position park on the trash page 0: same
        # block as their neighbours, so their copy is skipped
        lp = logical(j, pos[bi])
        entry = lp if window is None else lp % mp
        return (jnp.where(lp * page <= pos[bi], tbl[bi * mp + entry], 0),
                0, 0)

    q_spec = pl.BlockSpec((1, hkv, gp, d), lambda bi, j, tbl, pos:
                          (bi, 0, 0, 0))
    page_spec = pl.BlockSpec((1, page, hd), page_of)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, mp),
            in_specs=[q_spec, page_spec, page_spec], out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((hkv, gp, d), jnp.float32),
                            pltpu.VMEM((hkv, gp, _LANE), jnp.float32),
                            pltpu.VMEM((hkv, gp, _LANE), jnp.float32)]),
        interpret=_interpret(),
    )(page_table.reshape(-1), pos, qg, k_pool, v_pool)
    return out[:, :, :g].reshape(b, h, d)


# Two names for one walk, so that the trace tells a window layer's calls
# from a full layer's (`paged_attn_window_ms`, `paged_attn_full_ms`).
@jax.jit
def _paged_gqa_full(q, k_pool, v_pool, page_table, pos):
    return _page_walk_gqa(q, k_pool, v_pool, page_table, pos)


@partial(jax.jit, static_argnames=("window",))
def _paged_gqa_window(q, k_pool, v_pool, page_table, pos, window: int):
    return _page_walk_gqa(q, k_pool, v_pool, page_table, pos, window)


def _gather_pages(pool, page_table, d):
    """pool [NP, page, Hkv*D] (or scales [NP, page, Hkv], d=None) ->
    the slots' logical view [B, MP*page, Hkv, D] ([B, MP*page, Hkv])."""
    b, mp = page_table.shape
    rows = pool[page_table].reshape(b, mp * pool.shape[1], pool.shape[2])
    return rows if d is None else rows.reshape(*rows.shape[:2], -1, d)


def _xla_paged_int8(q, kq_pool, ks_pool, vq_pool, vs_pool, page_table, pos):
    """Gather fallback with the same quant factoring as _cache_attention."""
    b, h, d = q.shape
    kq = _gather_pages(kq_pool, page_table, d)
    vq = _gather_pages(vq_pool, page_table, d)
    ks = _gather_pages(ks_pool, page_table, None)
    vs = _gather_pages(vs_pool, page_table, None)
    hk = kq.shape[2]
    if hk != h:
        kq = jnp.repeat(kq, h // hk, axis=2)
        vq = jnp.repeat(vq, h // hk, axis=2)
        ks = jnp.repeat(ks, h // hk, axis=2)
        vs = jnp.repeat(vs, h // hk, axis=2)
    sc = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                    kq.astype(jnp.float32))
    sc = sc * ks.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
    valid = jnp.arange(kq.shape[1])[None, None, :] <= pos[:, None, None]
    sc = jnp.where(valid, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1) * vs.transpose(0, 2, 1)
    return jnp.einsum("bhk,bkhd->bhd", p, vq.astype(jnp.float32))


def paged_decode_attention_int8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                                page_table, pos):
    """int8 paged decode attention (the 4-tuple cache form): page-walk
    kernel when eligible, quant-factored XLA gather otherwise."""
    if (kq_pool.shape[2] == q.shape[1] * q.shape[2]
            and paged_kernel_ok(q, kq_pool)):
        return _paged_pallas_int8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                                  page_table.astype(jnp.int32),
                                  pos.astype(jnp.int32))
    return _xla_paged_int8(q, kq_pool, ks_pool, vq_pool, vs_pool,
                           page_table, pos)


def _gathered_attention(q, k_pool, v_pool, page_table, valid):
    """Softmax attention of q [B, H, D] over each slot's gathered pages,
    keys masked by `valid` [B, MP*page].  Mirrors
    models/transformer._cache_attention for the paged branch.  GQA pools
    (hk < h) expand to the query head count after the gather."""
    b, h, d = q.shape
    k_log = _gather_pages(k_pool, page_table, d)
    v_log = _gather_pages(v_pool, page_table, d)
    hk = k_log.shape[2]
    if hk != h:
        k_log = jnp.repeat(k_log, h // hk, axis=2)
        v_log = jnp.repeat(v_log, h // hk, axis=2)
    sc = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                    k_log.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    sc = jnp.where(valid[:, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v_log.astype(jnp.float32))


def _xla_paged(q, k_pool, v_pool, page_table, pos):
    """Reference semantics: gather pages -> masked softmax attention
    over positions <= pos."""
    n_keys = page_table.shape[1] * k_pool.shape[1]
    return _gathered_attention(q, k_pool, v_pool, page_table,
                               jnp.arange(n_keys)[None, :] <= pos[:, None])


def _xla_paged_window(q, k_pool, v_pool, page_table, pos, window: int):
    """Gather semantics of a window layer's RING table: entry e of slot
    b holds the newest logical page lp <= pos // page with lp % MP == e.
    Keys outside (pos - window, pos] are masked, which also hides the
    rows of a recycled page that the new page has not overwritten yet."""
    page, mp = k_pool.shape[1], page_table.shape[1]
    cur = pos[:, None] // page                            # [B, 1]
    lp = cur - (cur - jnp.arange(mp)[None]) % mp          # [B, MP]
    kpos = (lp[:, :, None] * page
            + jnp.arange(page)[None, None]).reshape(-1, mp * page)
    valid = ((kpos >= 0) & (kpos <= pos[:, None])
             & (kpos > pos[:, None] - window))
    return _gathered_attention(q, k_pool, v_pool, page_table, valid)


def paged_decode_attention(q, k_pool, v_pool, page_table, pos,
                           window=None):
    """Single-token paged decode attention: q [B, H, D] over page pools
    [NP, page, Hkv*D] addressed by table [B, MP] at per-slot positions
    `pos` [B].  Pallas page-walk kernel when the shape allows, XLA
    gather otherwise — identical numerics either way.  `window` (static)
    makes the table a ring of window / page + 1 pages (`_page_walk_gqa`)
    and hides keys at or before pos - window."""
    tbl, pos = page_table.astype(jnp.int32), pos.astype(jnp.int32)
    if paged_kernel_ok(q, k_pool):
        if k_pool.shape[2] != q.shape[1] * q.shape[2]:
            if window is not None:
                return _paged_gqa_window(q, k_pool, v_pool, tbl, pos,
                                         window=window)
            return _paged_gqa_full(q, k_pool, v_pool, tbl, pos)
        if window is None:
            return _paged_pallas(q, k_pool, v_pool, tbl, pos)
    if window is not None:
        return _xla_paged_window(q, k_pool, v_pool, tbl, pos, window)
    return _xla_paged(q, k_pool, v_pool, page_table, pos)


# ---- latent attention (MLA): one pool of [c, kr] rows ----------------------
_MLA_CHUNK = 8          # pages a loop step copies and multiplies at once


def _mla_query_parts(q_abs, dtype):
    """The absorbed query q_abs [B, H, W] float32 as the operands of a
    product at the pool's `dtype`: hi + lo, hi the rounding to `dtype`
    and lo the rounding of what that lost.  Against bf16 rows the two
    products' sum carries q_abs to 16 bits of mantissa, which is what
    float32 accumulation of the unabsorbed product would keep (a float32
    pool takes hi alone: lo is zero).  The roundings are ones the
    compiler keeps (`rounded_to`): with a bare `astype` pair lo comes
    out zero and the walk reads a bf16 query."""
    from .quant import rounded_to

    hi = rounded_to(q_abs, dtype)
    return hi.astype(dtype), rounded_to(q_abs - hi, dtype).astype(dtype)


def _mla_page_walk(hi, lo, pool, page_table, pos, rank: int):
    """hi, lo [B, H, W] (`_mla_query_parts`); pool [NP, page, W], a row
    the latent (first `rank`) and the rope key; table [B, MP] i32; pos
    [B] i32 -> softmax((hi + lo) . row) . row[:rank], [B, H, rank] f32.

    One grid step a slot.  Inside it a loop over the slot's LIVE pages,
    `_MLA_CHUNK` at a time (its trip count is the slot's own: pages past
    the write position are neither copied nor multiplied, and a parked
    slot takes one chunk of the trash page): each page is copied from
    the pool in HBM into one of two VMEM buffers by its own DMA, the next
    chunk's copies running under this chunk's products.  All heads read
    the one row, so a chunk is two MXU products: [2H, W] x [W, chunk
    rows] for the scores of hi and lo at once, [H, chunk rows] x [chunk
    rows, rank] for the values; online softmax in float32 scratch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, w = hi.shape
    _, page, _ = pool.shape
    mp = page_table.shape[1]
    g = min(_MLA_CHUNK, mp)
    span = g * page
    split = pool.dtype != jnp.float32
    q2 = jnp.concatenate([hi, lo], 1) if split else hi
    hq = q2.shape[1]

    def kernel(tbl_ref, pos_ref, q_ref, pool_ref, o_ref, buf, sem,
               o_acc, m_acc, l_acc):
        bi = pl.program_id(0)
        p_b = pos_ref[bi]
        n_chunks = (p_b // page + g) // g

        def copies(chunk, slot):
            return [pltpu.make_async_copy(
                pool_ref.at[tbl_ref[bi * mp + jnp.minimum(chunk * g + i,
                                                          mp - 1)]],
                buf.at[slot, pl.ds(i * page, page)], sem.at[slot, i])
                for i in range(g)]

        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        for cp in copies(0, 0):
            cp.start()

        def body(c, _carry):
            slot = c % 2

            @pl.when(c + 1 < n_chunks)
            def _ahead():
                for cp in copies(c + 1, 1 - slot):
                    cp.start()

            for cp in copies(c, slot):
                cp.wait()
            rows = buf[slot]                              # [span, W]
            sc = jax.lax.dot_general(
                q_ref[0], rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [2H, span]
            if split:
                sc = sc[:h] + sc[h:]
            cols = c * span + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            seen = cols <= p_b
            sc = jnp.where(seen, sc, _NEG_INF)
            m_prev = jnp.max(m_acc[...], axis=-1, keepdims=True)
            l_prev = jnp.max(l_acc[...], axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(seen, jnp.exp(sc - m_new), 0.0)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            o_acc[...] = o_acc[...] * corr + jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :rank],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)
            l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)
            return _carry

        jax.lax.fori_loop(0, n_chunks, body, 0)
        l_fin = jnp.max(l_acc[...], axis=-1, keepdims=True)
        o_ref[0] = o_acc[...] / jnp.maximum(l_fin, 1e-20)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, hq, w), lambda bi, tbl, pos:
                                   (bi, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, rank), lambda bi, tbl, pos:
                                   (bi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, span, w), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, g)),
                            pltpu.VMEM((h, rank), jnp.float32),
                            pltpu.VMEM((h, _LANE), jnp.float32),
                            pltpu.VMEM((h, _LANE), jnp.float32)]),
        interpret=_interpret(),
    )(page_table.reshape(-1), pos, q2, pool)


# `mla_decode_ms` finds the walk by `^_paged_mla`.
@partial(jax.jit, static_argnames=("rank",))
def _paged_mla(hi, lo, pool, page_table, pos, rank: int):
    return _mla_page_walk(hi, lo, pool, page_table, pos, rank)


def _xla_paged_mla(hi, lo, pool, page_table, pos, rank: int):
    """Gather semantics of the same: every slot's pages gathered,
    positions <= pos attended, in float32."""
    q = hi.astype(jnp.float32) + lo.astype(jnp.float32)
    rows = _gather_pages(pool, page_table, None).astype(jnp.float32)
    sc = jnp.einsum("bhw,bkw->bhk", q, rows,
                    precision=jax.lax.Precision.HIGHEST)
    valid = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(valid[:, None, :], sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhk,bkr->bhr", p, rows[..., :rank],
                      precision=jax.lax.Precision.HIGHEST)


def mla_kernel_ok(pool, rank: int) -> bool:
    """A lane-wide latent in lane-wide rows, a page of whole sublane
    tiles of the pool's dtype (a page is one DMA into a slice of the
    chunk's buffer), and two chunks of rows inside the VMEM budget."""
    import os

    _, page, w = pool.shape
    item = pool.dtype.itemsize
    return (not os.environ.get("MMLSPARK_NO_PAGED_KERNEL")
            and rank % _LANE == 0 and w % _LANE == 0
            and page % (_SUBLANE * max(1, 4 // item)) == 0
            and 2 * _MLA_CHUNK * page * w * item <= PALLAS_IMAGE_VMEM_BUDGET)


def paged_mla_attention(q_abs, pool, page_table, pos, rank: int,
                        kernel: bool = True):
    """Single-token decode attention in the latent space: the absorbed,
    scaled query q_abs [B, H, rank + rope] float32 against ONE pool of
    cached rows [NP, page, rank + rope] (latent `rank`, then rope key) under
    table [B, MP] at per-slot positions `pos` [B] -> (softmax(q_abs .
    row) . row[:rank] [B, H, rank] float32, the query as it was
    multiplied, float32).  The page walk where `kernel` and the shape
    allow, the gather composition otherwise."""
    hi, lo = _mla_query_parts(q_abs, pool.dtype)
    tbl, pos = page_table.astype(jnp.int32), pos.astype(jnp.int32)
    walk = _paged_mla if kernel and mla_kernel_ok(pool, rank) \
        else _xla_paged_mla
    return (walk(hi, lo, pool, tbl, pos, rank=rank),
            hi.astype(jnp.float32) + lo.astype(jnp.float32))
