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
  - Score tiles are square, `_pick_blocks(s, d, causal)` a side (512
    where it tiles S and d <= 128, else 256, 128, or S itself under
    128), and a kernel LOOPS over tiles inside a grid step: a grid step
    costs about 0.4 us on a v5e whatever it does, and at the LM cell's
    [96, 1024, 64] the one-tile-a-step grids before PR 30 took 4,608
    of them a layer (now 576).  The forward's
    grid is (B*H, S / block_q, S / k_major): a step holds a query block
    and up to 4,096 keys (`_kv_major`; all of a training sequence) and
    walks their key tiles; longer sequences stream major blocks, the
    online softmax's m / l / o carried in VMEM scratch, so VMEM use is
    O(block_q * block_k + k_major * D), independent of S.
  - The causal schedule (`_k_range`, `_q_range`, `causal_tiles`): tiles
    above the diagonal are never started (nor copied: index maps park
    on the last block that shows something), tiles wholly under it take
    no mask, and only the tiles it crosses pay the iota / compare /
    select of `_masked` — the same for the tiles `kv_valid` crosses
    when S was padded.  The softmax scale is folded into the query
    block once (`_scaled`), not multiplied into every score tile.
  - Tiles are computed TRANSPOSED, keys down the sublanes and queries
    along the lanes (s^T = K Q^T): per-query statistics (m, l, the
    logsumexp, delta) are then [1, block_q] rows that broadcast down
    the sublanes for nothing and reduce without crossing lanes, where a
    [block_q, 1] column costs block_q / 8 registers an operation; the
    logsumexp travels between forward and backward as [B*H, S] f32,
    one lane-dense row a query block, never lane-broadcast.
  - scores/softmax statistics and every accumulator in f32; all matmuls
    via dot_general at the inputs' dtype with f32 accumulation, p and
    ds cast at the consuming matmul; outputs (o, dq, dk, dv) leave at
    q's dtype, which is what the model keeps (f32 stays f32).

Training: fused_attention carries a custom VJP whose BACKWARD is the
flash-attention backward, recomputing each score tile in VMEM from the
forward's saved logsumexp — the dense-XLA backward materialized f32
[B, H, S, S] score tensors per layer.  Where a head's Q, dO and f32 dQ
fit VMEM (`_fused_bwd_fits`: to S=8,192 at D <= 128 and 4,096 at
D = 256 in bf16, the kernel asking the compiler for 32 MiB where its
estimate passes the 16 MiB a v5e kernel gets by default) it is ONE kernel,
`_attention_bwd_dkdv_dq`: key blocks on the grid, a loop over the query
blocks that see them, each tile's p and ds computed once for dV, dK
and dQ (5 matmuls and one vector pass a tile).  Longer sequences take
the split pair (`_attention_bwd_dkdv` streaming Q blocks,
`_attention_bwd_dq` streaming K blocks: 7 matmuls, two passes), O(block)
in VMEM, on the same tiles, schedule and `_bwd_tile`.  Shapes
`kernel_ok` declines run the XLA composition forward and backward.
There is no other fallback: a shape `kernel_ok` admits and the compiler
refuses raises (under an outer jit, when the outer program compiles).

Serving: `prefill_attention` is the admission's flash forward, the same
plan (tiles looped in a grid step, transposed, `_scaled`, `_hide`, q's
dtype out) for what an admission adds: grouped heads (a KV head's query
heads packed into one step, side by side along a tile's lanes), an
optional window, a v head narrower than q/k, and the prompt's own
LENGTH inside its bucket (`lengths`, scalar-prefetched): the tiles
visited are exactly those in which a row of the prompt sees a key
(`_prefill_k_range`; `prefill_tile_counts` counts them), the bucket's
padding costs a zero fill.  Its tiles
are chosen for what an instance costs to COMPILE as well as for ms a
call (`_pick_prefill_blocks`): a serving cell warms some twenty admission
programs of an instance a layer before its first request.

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

__all__ = ["fused_attention", "attention_fits_vmem", "kernel_ok",
           "prefill_attention", "prefill_attention_ok",
           "prefill_tile_counts"]

_LANE = 128
_NEG_INF = -1e30  # finite stand-in: -inf arithmetic is fragile on Mosaic
# VMEM of the one-kernel backward, on the admission kernel's model
# (`_PREFILL_VMEM_*`): what its estimate (`_fused_bwd_vmem`) may come to
# under the 16 MiB a v5e kernel gets by default, with room for what the
# estimate leaves out; past that, the limit the call asks the compiler for
# (a v5e core has 128 MiB) and what the estimate may come to under it.  A
# head's Q, dO and f32 dQ stay resident, so the estimate grows with S x D:
# 20.75 MiB at S = 4,096, D = 256 in bf16, where the split pair would
# compute every score tile twice
_FUSED_BWD_VMEM_DEFAULT = 12 * 1024 * 1024
_FUSED_BWD_VMEM_LIMIT = 32 * 1024 * 1024
_FUSED_BWD_VMEM_BUDGET = 24 * 1024 * 1024


# ---- the tile schedule -----------------------------------------------------
def _pick_blocks(s: int, d: int, causal: bool) -> tuple:
    """(block_q, block_k) of the training kernels' score tiles, from the
    shape alone: square, the largest of 512, 256, 128 that tiles s (512
    only to d = 128: a wider head in f32 would push the forward past
    its VMEM estimate), s itself under 128.  Measured on the chip at
    [96, 1024, 64] bf16 causal (PERF.md section 6, PR 30): forward /
    fused backward 0.44 / 0.88 ms a call at 512, 0.70 / 0.90 at 256,
    1.44 / 1.80 at 128 and no better for an unequal pair — the D=64
    matmuls fill half the MXU and each tile pays its own pipeline fill,
    so fewer, larger tiles win although 512 computes 75% of S^2 under
    the diagonal where 256 computes 62.5% and 128 56%.  `causal` is in
    the signature because the choice is its to change; today both
    schedules take the same tile."""
    del causal
    for blk in (512, 256, _LANE):
        if s % blk == 0 and (blk <= 256 or d <= _LANE):
            return blk, blk
    return s, s  # s < 128: one tile (s itself must divide by 8)


def _k_range(r0, block_q: int, block_k: int):
    """Causal, query rows [r0, r0 + block_q): key blocks [0, n_full) lie
    wholly at or under the diagonal (no mask), [n_full, n_vis) are
    crossed by it (mask), the rest show nothing.  Ints or traced."""
    return (r0 + 1) // block_k, (r0 + block_q - 1) // block_k + 1


def _q_range(c0, block_q: int, block_k: int):
    """Causal, key columns [c0, c0 + block_k): query blocks
    [i_first, i_full) are crossed by the diagonal, [i_full, ...) lie
    wholly under it, those before i_first see none of the keys."""
    return c0 // block_q, (c0 + block_k + block_q - 2) // block_q


def _tile_kind(r0, c0, block_q: int, block_k: int, causal: bool, kv_valid):
    """(visible, masked) of the one tile at query row r0, key column c0:
    the same schedule asked tile by tile (the split backward's grids)."""
    visible, masked = True, False
    if causal:
        visible = r0 + block_q - 1 >= c0
        masked = c0 + block_k - 1 > r0
    if kv_valid is not None:
        masked = masked | (c0 + block_k > kv_valid)
    return visible, masked


def causal_tiles(s: int, block_q: int, block_k: int) -> list:
    """[(query block, key block, masked)] of every tile the causal
    kernels compute at sequence length s, in the forward's order: the
    schedule as a pure function, held by the tests to "every tile with
    at least one unmasked element, masked where it has a masked one"."""
    tiles = []
    for qi in range(s // block_q):
        n_full, n_vis = _k_range(qi * block_q, block_q, block_k)
        tiles += [(qi, ki, ki >= n_full)
                  for ki in range(min(n_vis, s // block_k))]
    return tiles


def _kv_major(s: int, d: int, itemsize: int, block_k: int, dv=None,
              budget: int = PALLAS_IMAGE_VMEM_BUDGET // 2) -> int:
    """Keys a forward grid step holds in VMEM: the largest multiple of
    block_k that tiles s and keeps K and V (heads `dv` wide, d if None),
    double-buffered, inside `budget` (the training forward's: half the
    image budget).  Up to 4,096 keys at d <= 128 in bf16 there, so a
    training sequence is ONE grid step a query block and its key tiles
    a loop inside that step; past that the major blocks stream."""
    d_l = _pad_up(d, _LANE) + _pad_up(d if dv is None else dv, _LANE)
    n = s // block_k
    for m in range(n, 0, -1):
        if n % m == 0 and 2 * m * block_k * d_l * itemsize <= budget:
            return m * block_k
    return block_k


def _fused_bwd_vmem(s: int, d: int, itemsize: int = 2) -> int:
    """VMEM estimate of the one-kernel backward, in bytes.  It keeps a
    head's Q and dO (as given, and Q scaled), the f32 dQ accumulator and
    the dQ output resident beside one key block's tiles: S=1024, D=64 in
    bf16 comes to 7 MB, 3 of them the f32 tiles."""
    block_q, block_k = _pick_blocks(s, d, True)
    d_l = _pad_up(d, _LANE)
    resident = (s * d_l * (7 * itemsize + 4)     # q, dO x2; q scaled; dQ x2
                + 4 * 8 * s * 4)                 # lse, delta rows x2
    step = (8 * block_k * d_l * itemsize         # K, V in; dK, dV out, x2
            + 2 * block_k * d_l * 4              # dK, dV accumulators
            + 3 * block_q * block_k * 4)         # p / dp / ds (f32)
    return resident + step


def _fused_bwd_fits(s: int, d: int, itemsize: int = 2) -> bool:
    """Does the one-kernel backward fit `_FUSED_BWD_VMEM_BUDGET`?  In
    bf16 the last length on the 128 grid that fits is S=9,856 at
    D <= 128 and 4,992 at D = 192 or 256 (8,192 and 4,096 of the powers
    of two; in f32 5,504 and 2,688); the split pair, O(block) in VMEM,
    takes the sequences past it.  A shape whose estimate passes
    `_FUSED_BWD_VMEM_DEFAULT` asks the compiler for
    `_FUSED_BWD_VMEM_LIMIT` (in bf16 no power of two to S=2,048 does, at
    any D to 256)."""
    return _fused_bwd_vmem(s, d, itemsize) <= _FUSED_BWD_VMEM_BUDGET


def attention_fits_vmem(s: int, d: int, itemsize: int = 2) -> bool:
    """Per-grid-step VMEM estimate of the forward and of the SPLIT
    backward — O(block_q * block_k) and, in the forward, the K/V major
    block `_kv_major` bounds; NOT O(S).  Taking the kernel path commits
    callers to the flash backward too (custom_vjp): both must fit.  The
    fused backward is taken where `_fused_bwd_fits` besides."""
    d_l = _pad_up(d, _LANE)
    block_q, block_k = _pick_blocks(s, d, True)
    k_major = _kv_major(s, d, itemsize, block_k)
    fwd = (4 * k_major * d_l * itemsize       # K + V major blocks, x2
           + 4 * block_q * d_l * itemsize     # Q in, O out, x2
           + 2 * block_q * block_k * 4        # scores + probs (f32)
           + block_q * d_l * 4                # o^T scratch
           + 2 * 8 * block_q * 4)             # m / l rows
    bwd = (4 * block_k * d_l * itemsize       # K + V blocks, x2
           + 4 * block_q * d_l * itemsize     # Q + dO blocks, x2
           + 4 * block_k * d_l * itemsize     # dK + dV (or dQ) out, x2
           + 4 * 8 * block_q * 4              # lse + delta rows, x2
           + 3 * block_q * block_k * 4        # p / dp / ds (f32)
           + 2 * block_k * d_l * 4)           # dK + dV accumulators
    return max(fwd, bwd) <= PALLAS_IMAGE_VMEM_BUDGET


# ---- what every kernel does to a tile --------------------------------------
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _scaled(qb, scale: float):
    """The query block times the softmax scale, at its own dtype: [bq, D]
    work once a block, where scaling the scores was [bq, bk] work a tile
    (exact at D=64, a power of two)."""
    return (qb.astype(jnp.float32) * scale).astype(qb.dtype)


def _masked(sc, q0, k0, q_axis: int, causal: bool, kv_valid):
    """The causal and/or KV-padding mask on one score tile whose axis
    `q_axis` runs over queries from q0 and whose other axis over keys
    from k0 (`_hide` at the tile's own positions).  Only tiles the
    diagonal (or `kv_valid`) crosses come here."""
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1 - q_axis)
    qpos = None
    if causal:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, q_axis)
    return _hide(sc, qpos, kpos, causal, kv_valid)


def _hide(sc, qpos, kpos, causal: bool, kv_valid, window=None):
    """Scores `sc` with every (query, key) pair hidden that the causal
    rule, `kv_valid` or `window` hides — THE shared definition for the
    forward, the backward and the admission kernels, so mask and
    _NEG_INF semantics cannot desynchronize.  `qpos` / `kpos`: the
    positions along the tile's two axes, of the tile's shape or thin (a
    row and a column that broadcast).  `kv_valid` (static) masks key
    columns >= the true sequence length when S was padded up to the
    block grid: zero-padded K rows would otherwise score 0 and steal
    softmax mass from every valid query.  `window` (static, causal
    only) hides the keys at or before query - window."""
    mask = None
    if causal:
        mask = qpos >= kpos
        if window is not None:
            mask = mask & (kpos > qpos - window)
    if kv_valid is not None:
        kv_mask = kpos < kv_valid
        mask = kv_mask if mask is None else (mask & kv_mask)
    return jnp.where(mask, sc, _NEG_INF)


def _bwd_tile(qs, kb, vb, dob, lse, dlt, masked, r0, c0, causal, kv_valid):
    """One backward tile, TRANSPOSED (keys down the sublanes, queries
    along the lanes, so the per-query lse and delta are [1, bq] rows that
    broadcast down for nothing) — shared by the fused kernel and the
    split pair: p^T = exp(K Qs^T - lse), recomputed from the forward's
    logsumexp (exact, no renormalization pass), and
    ds^T = p^T * (V dO^T - delta) WITHOUT the scale: Qs carries it into
    dK = ds^T Qs, and dQ takes it once at the end.  All f32; -> (p^T,
    ds^T) cast to the consuming matmuls' operand dtype."""
    s_t = _dot(kb, qs, _NT)                              # [bk, bq] f32
    if masked:
        s_t = _masked(s_t, r0, c0, 1, causal, kv_valid)
    p_t = jnp.exp(s_t - lse)
    ds_t = p_t * (_dot(vb, dob, _NT) - dlt)
    return p_t.astype(dob.dtype), ds_t.astype(qs.dtype)


def _loop(lo, hi, body, carry=None):
    """fori_loop, not emitted for a range that is statically empty."""
    if isinstance(lo, int) and isinstance(hi, int) and hi <= lo:
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _stat_rows(x, block_q: int):
    """[BH, S] per-query statistics -> [BH, S / block_q, 1, block_q]: one
    lane-dense row a query block, which a kernel indexes by the block."""
    bh, s = x.shape
    return x.reshape(bh, s // block_q, 1, block_q)


@partial(jax.jit, static_argnames=("causal", "scale", "kv_valid"))
def _attention_pallas(q, k, v, causal: bool, scale: float,
                      kv_valid=None):
    """q,k,v: [BH, S, D] (D as `_kernel_d` has it) -> (o [BH, S, D] at
    q's dtype, logsumexp [BH, S] f32).  `scale` is 1/sqrt(TRUE head
    dim) — a padded D must not leak into the softmax temperature.

    grid (BH, S / block_q, S / k_major), the last axis sequential: a step
    holds one query block and `k_major` keys and LOOPS over their
    block_k-wide tiles, first those wholly under the diagonal (no mask),
    then those it crosses; the tiles above it are never started, and a
    major block wholly above it is neither computed nor copied (its
    index map parks on the last one the query block sees)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    block_q, block_k = _pick_blocks(s, d, causal)
    k_major = _kv_major(s, d, q.dtype.itemsize, block_k)
    n_q, n_kb, n_major, n_sub = (s // block_q, s // block_k, s // k_major,
                                 k_major // block_k)

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc, m_acc, l_acc):
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        r0 = qi * block_q

        @pl.when(kj == 0)
        def _init():
            o_acc[...] = jnp.zeros_like(o_acc)
            m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
            l_acc[...] = jnp.zeros_like(l_acc)

        if causal:
            n_full, n_vis = _k_range(r0, block_q, block_k)
        elif kv_valid is not None:
            n_full, n_vis = kv_valid // block_k, -(-kv_valid // block_k)
        else:
            n_full = n_vis = n_kb
        if causal or n_major > 1:     # this major block's share of them
            n_full = jnp.clip(n_full - kj * n_sub, 0, n_sub)
            n_vis = jnp.clip(n_vis - kj * n_sub, 0, n_sub)
        qs = _scaled(q_ref[0], scale)

        def tile(t, carry, masked):
            m_prev, l_prev, acc = carry                  # [1, bq] x2, [D, bq]
            c0 = pl.multiple_of(t * block_k, block_k)
            kb = k_ref[0, pl.ds(c0, block_k), :]
            vb = v_ref[0, pl.ds(c0, block_k), :]
            s_t = _dot(kb, qs, _NT)                      # [bk, bq] f32
            if masked:
                s_t = _masked(s_t, r0, kj * k_major + c0, 1, causal,
                              kv_valid)
            m_new = jnp.maximum(m_prev, jnp.max(s_t, axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # a query this tile hides wholly keeps the m an earlier tile
            # gave it (every query's FIRST tile shows it key 0), so its
            # p is exp(-1e30 - m) = 0
            p_t = jnp.exp(s_t - m_new)
            l_new = l_prev * corr + jnp.sum(p_t, axis=0, keepdims=True)
            return m_new, l_new, acc * corr + _dot(vb, p_t.astype(vb.dtype),
                                                   _TN)

        # TRANSPOSED like the backward: keys down the sublanes, queries
        # along the lanes.  The online softmax's m and l are then [1, bq]
        # ROWS (two registers, where a [bq, 1] column is bq / 8 and its
        # row maximum a cross-lane reduction), the output accumulates as
        # o^T [D, bq], and the logsumexp leaves in the backward's layout
        carry = (m_acc[...], l_acc[...], o_acc[...])
        carry = _loop(0, n_full, partial(tile, masked=False), carry)
        if causal or kv_valid is not None:
            carry = _loop(n_full, n_vis, partial(tile, masked=True), carry)
        m_acc[...], l_acc[...], o_acc[...] = carry

        @pl.when(kj == n_major - 1)
        def _finish():
            m_fin, l_fin, acc = carry
            # fully-masked queries (possible only with non-causal all-pad
            # inputs) keep l=0; guard the divide
            l_fin = jnp.maximum(l_fin, 1e-20)
            o_ref[0] = (acc / l_fin).T.astype(o_ref.dtype)
            lse_ref[0, 0] = m_fin + jnp.log(l_fin)

    def kv_block(b, i, j):
        if causal and n_major > 1:
            # major blocks above the diagonal park on its own: no copy
            j = jnp.minimum(j, (i * block_q + block_q - 1) // k_major)
        return (b, j, 0)

    o, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, n_q, 1, block_q),
                                        jnp.float32)),
        grid=(bh, n_q, n_major),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, k_major, d), kv_block),
            pl.BlockSpec((1, k_major, d), kv_block),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, i, j: (b, i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((d, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return o, lse.reshape(bh, s)


def _xla_attention(q, k, v, causal: bool):
    from ..parallel.ring_attention import full_attention

    return full_attention(q, k, v, causal=causal).astype(q.dtype)


@partial(jax.jit, static_argnames=("causal", "scale", "kv_valid"))
def _attention_bwd_dkdv_dq(q, k, v, do, lse, delta, causal: bool,
                           scale: float, kv_valid=None):
    """The whole flash backward in ONE kernel, where a head's dQ fits
    VMEM (`_fused_bwd_fits`): -> (dq, dk, dv) [BH, S, D] at q's dtype.
    q, k, v, do: [BH, S, D]; lse, delta: [BH, S] f32.

    grid (BH, S / block_k): a step holds one key block and the head's
    whole Q, dO, lse and delta (copied once a head: their block index
    does not move), and LOOPS over the query blocks that see the keys —
    first those the diagonal crosses, then those wholly under it —
    recomputing each tile's p and ds ONCE (`_bwd_tile`) for all three
    gradients: dV += p^T dO and dK += ds^T Qs in f32 scratch, written a
    step, and dQ^T[block] += K^T ds^T into a head-resident f32
    accumulator ([S / block_q, D, block_q]: the small K block is the
    operand transposed, not the tile), turned and written once a head.
    Five matmuls and one vector pass a tile, as FlashAttention's
    backward has them.  A shape whose VMEM estimate passes
    `_FUSED_BWD_VMEM_DEFAULT` asks the compiler for
    `_FUSED_BWD_VMEM_LIMIT`; the others pass no compiler parameters, so
    their kernel is the one the default limit compiles.  The name starts with
    `_attention_bwd_dkdv`: the benchmark's trace metrics find the kernel
    by that."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    block_q, block_k = _pick_blocks(s, d, causal)
    n_q, n_kb = s // block_q, s // block_k
    params = None
    if _fused_bwd_vmem(s, d, q.dtype.itemsize) > _FUSED_BWD_VMEM_DEFAULT:
        params = pltpu.CompilerParams(vmem_limit_bytes=_FUSED_BWD_VMEM_LIMIT)

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, dk_ref, dv_ref, qs_ref, dq_acc, dk_acc, dv_acc):
        kj = pl.program_id(1)
        c0 = kj * block_k

        @pl.when(kj == 0)
        def _head():
            qs_ref[...] = _scaled(q_ref[0], scale)
            dq_acc[...] = jnp.zeros_like(dq_acc)

        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        kb, vb = k_ref[0], v_ref[0]

        def tile(i, _, masked):
            r0 = pl.multiple_of(i * block_q, block_q)
            qs = qs_ref[pl.ds(r0, block_q), :]
            dob = do_ref[0, pl.ds(r0, block_q), :]
            p_t, ds_t = _bwd_tile(qs, kb, vb, dob, lse_ref[0, i],
                                  dl_ref[0, i], masked, r0, c0, causal,
                                  kv_valid)
            dv_acc[...] += _dot(p_t, dob, _NN)               # [bk, D]
            dk_acc[...] += _dot(ds_t, qs, _NN)               # [bk, D]
            dq_acc[i] += _dot(kb, ds_t, _TN)                 # [D, bq]

        if causal:
            i_first, i_full = _q_range(c0, block_q, block_k)
            i_full = jnp.minimum(i_full, n_q)
        elif kv_valid is not None:   # a key block with padding in it
            i_first, i_full = 0, jnp.where(c0 + block_k > kv_valid, n_q, 0)
        else:
            i_first = i_full = 0
        _loop(i_first, i_full, partial(tile, masked=True))
        _loop(i_full, n_q, partial(tile, masked=False))
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

        @pl.when(kj == n_kb - 1)
        def _finish():
            for i in range(n_q):
                dq_ref[0, i * block_q:(i + 1) * block_q, :] = (
                    dq_acc[i] * scale).T.astype(dq_ref.dtype)

    head = pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0))
    stat = pl.BlockSpec((1, n_q, 1, block_q), lambda b, j: (b, 0, 0, 0))
    keys = pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0))
    return pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct((bh, s, d), q.dtype)
                        for _ in range(3)),
        grid=(bh, n_kb),
        in_specs=[head, keys, keys, head, stat, stat],
        out_specs=(head, keys, keys),
        scratch_shapes=[
            pltpu.VMEM((s, d), q.dtype),
            pltpu.VMEM((n_q, d, block_q), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=_interpret(),
    )(q, k, v, do, _stat_rows(lse, block_q), _stat_rows(delta, block_q))


def _split_bwd_specs(pl, s, d, block_q, block_k, causal, q_inner: bool):
    """Block specs of the split pair's grids (BH, outer, inner), one tile
    a step: Q blocks innermost for dK/dV, K blocks innermost for dQ.
    Under `causal` the inner axis' index maps park on the first (last)
    block the outer one sees, so a tile above the diagonal is not copied
    either.  -> (rows, keys, stat) for [BH, S, D] by query block, by key
    block, and the statistics' rows."""
    def qi_kj(outer, inner):
        qi, kj = (inner, outer) if q_inner else (outer, inner)
        if causal and q_inner:
            qi = jnp.maximum(qi, _q_range(kj * block_k, block_q,
                                          block_k)[0])
        if causal and not q_inner:
            kj = jnp.minimum(kj, _k_range(qi * block_q, block_q,
                                          block_k)[1] - 1)
        return qi, kj

    rows = pl.BlockSpec((1, block_q, d),
                        lambda b, o, n: (b, qi_kj(o, n)[0], 0))
    keys = pl.BlockSpec((1, block_k, d),
                        lambda b, o, n: (b, qi_kj(o, n)[1], 0))
    stat = pl.BlockSpec((1, 1, 1, block_q),
                        lambda b, o, n: (b, qi_kj(o, n)[0], 0, 0))
    return rows, keys, stat


def _split_bwd_step(update, r0, c0, block_q, block_k, causal, kv_valid):
    """Run `update(masked)` for the step's one tile: not at all above the
    diagonal, with the mask only where the diagonal or `kv_valid` crosses
    it."""
    from jax.experimental import pallas as pl

    visible, masked = _tile_kind(r0, c0, block_q, block_k, causal, kv_valid)
    if masked is False:     # static (not causal, not padded): every tile
        update(False)       # shows everything
        return
    pl.when(visible & masked)(partial(update, True))
    pl.when(visible & jnp.logical_not(masked))(partial(update, False))


@partial(jax.jit, static_argnames=("causal", "scale", "kv_valid"))
def _attention_bwd_dkdv(q, k, v, do, lse, delta, causal: bool, scale: float,
                        kv_valid=None):
    """dK/dV of the SPLIT backward (sequences past `_fused_bwd_fits`):
    grid (BH, n_k, n_q) with Q innermost — each (b, k-block) streams
    every visible Q/dO block, accumulating dV += p^T dO and
    dK += ds^T Qs in VMEM.  Shapes as the fused kernel's; -> (dk, dv)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    block_q, block_k = _pick_blocks(s, d, causal)
    n_q = s // block_q

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dk_ref, dv_ref, dk_acc, dv_acc):
        kj, qi = pl.program_id(1), pl.program_id(2)
        r0, c0 = qi * block_q, kj * block_k

        @pl.when(qi == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def update(masked):
            qs, dob = _scaled(q_ref[0], scale), do_ref[0]
            p_t, ds_t = _bwd_tile(qs, k_ref[0], v_ref[0], dob,
                                  lse_ref[0, 0], dl_ref[0, 0], masked,
                                  r0, c0, causal, kv_valid)
            dv_acc[...] += _dot(p_t, dob, _NN)
            dk_acc[...] += _dot(ds_t, qs, _NN)

        _split_bwd_step(update, r0, c0, block_q, block_k, causal, kv_valid)

        @pl.when(qi == n_q - 1)
        def _finish():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    rows, keys, stat = _split_bwd_specs(pl, s, d, block_q, block_k, causal,
                                        q_inner=True)
    out = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), q.dtype)),
        grid=(bh, s // block_k, n_q),
        in_specs=[rows, keys, keys, rows, stat, stat],
        out_specs=(out, out),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, do, _stat_rows(lse, block_q), _stat_rows(delta, block_q))


@partial(jax.jit, static_argnames=("causal", "scale", "kv_valid"))
def _attention_bwd_dq(q, k, v, do, lse, delta, causal: bool, scale: float,
                      kv_valid=None):
    """dQ of the split backward: grid (BH, n_q, n_k) with K innermost —
    the forward's order, accumulating dQ += ds K across the streamed K/V
    blocks and taking the scale at the end."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    block_q, block_k = _pick_blocks(s, d, causal)
    n_kb = s // block_k

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, dq_acc):
        qi, kj = pl.program_id(1), pl.program_id(2)
        r0, c0 = qi * block_q, kj * block_k

        @pl.when(kj == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        def update(masked):
            kb = k_ref[0]
            _p_t, ds_t = _bwd_tile(_scaled(q_ref[0], scale), kb, v_ref[0],
                                   do_ref[0], lse_ref[0, 0], dl_ref[0, 0],
                                   masked, r0, c0, causal, kv_valid)
            dq_acc[...] += _dot(kb, ds_t, _TN)               # [D, bq]

        _split_bwd_step(update, r0, c0, block_q, block_k, causal, kv_valid)

        @pl.when(kj == n_kb - 1)
        def _finish():
            dq_ref[0] = (dq_acc[...] * scale).T.astype(dq_ref.dtype)

    rows, keys, stat = _split_bwd_specs(pl, s, d, block_q, block_k, causal,
                                        q_inner=False)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=(bh, s // block_q, n_kb),
        in_specs=[rows, keys, keys, rows, stat, stat],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, do, _stat_rows(lse, block_q), _stat_rows(delta, block_q))


def _padded_len(s: int):
    """Kernel-grid sequence length for s, or None when the kernel should
    decline.  Non-block-multiple lengths (ViT's S=196, ragged text) pad
    up to the 128 grid with `kv_valid` masking — accepted only while the
    padded work stays within 1.5x of the true length, past which the
    masked blocks cost more than XLA dense's score traffic."""
    if s < 8:
        return None
    if s % min(_LANE, s) == 0 and s % 8 == 0:
        return s                       # native fit, no padding
    s_p = _pad_up(s, _LANE)
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
    """Drop-in for full_attention: (B, S, H, D) -> (B, S, H, D) at q's
    dtype, on the kernel arm and the XLA arm alike (full_attention
    itself hands back f32; bf16 callers cast on their next line anyway).

    VMEM-resident scores on TPU via Pallas (interpret mode elsewhere).
    Non-block-multiple S (ViT's 196, ragged text) pads up to the 128
    grid with kv_valid masking while the padded work stays within 1.5x
    of the true length (`_padded_len`); beyond that, and for head dim
    < 64 (lane padding wastes the MXU), the XLA composition runs
    instead — `kernel_ok(q)` is the public predicate.  Scale uses the
    TRUE head dim even when D pads to the 128 lane.  Differentiable:
    kernel-path shapes take the flash backward (one fused kernel where a
    head's dQ fits VMEM, the dK/dV + dQ pair past that; blockwise
    recompute from the saved logsumexp, with the forward's output kept
    at q's dtype and delta summed in f32 — matches the XLA gradients to
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
    return jnp.pad(x, ((0, 0), (0, s_p - s)) + ((0, 0),) * (x.ndim - 2))


def _kernel_d(d: int) -> int:
    """Head-dim the kernels run at: 64-multiples are native (64, 128,
    192, ... — every kernel compiles at the 64-minor tiles for a
    v5e in bf16 and f32, tests/test_aot_tpu_compile.py); everything else
    pads up to the 128 lane."""
    return d if d % 64 == 0 else _pad_up(d, _LANE)


def _run_kernel(q, k, v, causal: bool):
    """-> (out [B, S, H, D] at q's dtype, logsumexp [B*H, S] f32)."""
    b, s, h, d = q.shape
    d_p = _kernel_d(d)
    s_p = _padded_len(s)
    kv_valid = s if s_p != s else None
    scale = 1.0 / float(d) ** 0.5
    o, lse = _attention_pallas(
        _pad_seq(_to_bhsd(q, d_p), s_p), _pad_seq(_to_bhsd(k, d_p), s_p),
        _pad_seq(_to_bhsd(v, d_p), s_p), causal, scale, kv_valid)
    return _from_bhsd(o[:, :s], b, s, h, d), lse[:, :s]


def _fused_attention_fwd(q, k, v, causal):
    if kernel_ok(q):
        out, lse = _run_kernel(q, k, v, causal)
        return out, (q, k, v, out, lse)
    # the XLA backward recomputes from q/k/v alone — saving `out` here
    # would keep a dead [B, S, H, D] array alive until the backward
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
    # delta = rowsum(dO * O), summed in f32 on the TRUE head dim (`out`
    # is the residual at q's dtype).  Padded Q rows are inert by
    # construction: their dO rows pad to zero, so every dv/dk
    # contribution they touch is zero; lse/delta pad 0.
    delta = jnp.einsum("bshd,bshd->bhs", g, out,
                       preferred_element_type=jnp.float32)
    delta = _pad_seq(delta.reshape(b * h, s), s_p)
    lse = _pad_seq(lse, s_p)
    # matmul-heavy backward runs at the inputs' dtype (bf16 on the MXU)
    # with f32 accumulation, like the forward
    qp, kp, vp = (_pad_seq(_to_bhsd(x, d_p), s_p) for x in (q, k, v))
    dop = _pad_seq(_to_bhsd(g.astype(q.dtype), d_p), s_p)
    args = (qp, kp, vp, dop, lse, delta, causal, scale, kv_valid)
    if _fused_bwd_fits(s_p, d_p, q.dtype.itemsize):
        dq, dk, dv = _attention_bwd_dkdv_dq(*args)
    else:
        dk, dv = _attention_bwd_dkdv(*args)
        dq = _attention_bwd_dq(*args)
    return tuple(_from_bhsd(x[:, :s], b, s, h, d) for x in (dq, dk, dv))


fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)


# ---- prefill: causal, grouped heads, optional window (forward only) -------
def _div(a, b: int):
    """a // b for a >= 0 and an int b > 0, of ints or traced int32.
    Traced it is `lax.div` and not jnp's floor division, whose sign
    correction is a dozen operations the kernel's lowering pays for a
    division (4 ms each, some forty an instance, an instance a layer
    of every admission program a serving cell warms); so too `lax.min`,
    `max`, `rem`, `select` and `clamp` in the admission kernel's index
    arithmetic, where jnp's are jitted calls of their own."""
    return a // b if isinstance(a, int) else jax.lax.div(a, b)


def _prefill_k_range(r0, r_end, block_k: int, window):
    """Key tiles of query rows [r0, r_end) (those rows of a query block
    that are a prompt's own), causal and inside `window`: -> (a, b, c, n).
    Tiles [a, b) are crossed by the window's trailing edge (mask), [b, c)
    show every key to every row (no mask), [c, n) are crossed by the
    diagonal (mask); nothing before a (behind the window for every row)
    or from n on (above the diagonal of the last own row) is visited.
    `_k_range` with the window and the length beside the diagonal; ints
    or traced."""
    ints = isinstance(r0, int) and isinstance(r_end, int)
    hi, lo = (max, min) if ints else (jax.lax.max, jax.lax.min)
    n = _div(r_end + block_k - 1, block_k)
    c = lo(_div(r0 + 1, block_k), n)
    if window is None:
        return 0, 0, c, n
    a = _div(hi(r0 - window + 1, 0), block_k)
    b = lo(hi(_div(hi(r_end - window, 0) + block_k - 1, block_k), a), n)
    return a, b, hi(c, b), n


# VMEM of the admission kernel: what K and V of one KV head may take,
# double-buffered (8,192 keys at q/k 192 and v 128 in bf16: the longest
# bucket of `longcat-serve-long` stays resident), the limit the call asks
# the compiler for (a v5e core has 128 MiB; 16 is the default) and what
# the estimate may come to under it
_PREFILL_KV_BUDGET = 16 * 1024 * 1024
_PREFILL_VMEM_LIMIT = 32 * 1024 * 1024
_PREFILL_VMEM_BUDGET = 24 * 1024 * 1024
# a score tile's bounds, which are the kernel's set-up budget: Mosaic
# unrolls a tile's vector work, and an instance's compile seconds follow
# the tile's query columns first and its key rows second
# (`_pick_prefill_blocks`)
_PREFILL_LANES = 512
_PREFILL_KEYS = 256


def _pick_prefill_blocks(s: int, group: int) -> tuple:
    """(block_q, block_k, pack) of the admission kernel's score tiles,
    from the shape alone, by TWO measured columns: ms a call on the chip
    and compile seconds an instance (described v5e; PERF.md section 6,
    PR 35).  `pack` query heads of one KV head share a grid step, their
    query blocks side by side along the tile's lanes, so a key tile is
    read once for all of them: the group's largest divisor at 128 rows
    a head within 512 lanes; then the largest block_q of 512, 256, 128
    within the lanes, and key tiles of 256 rows, or of a query block's
    where that is longer (one head to a step).
    A tile visit costs about 0.5 us whatever its width (K and V latched
    into the MXU), which is why wide tiles win on the chip: 48 heads on
    8 KV heads at 128, a prompt of 3,000 in a 4,096 bucket, 1.06 ms at
    (256, 256, 6), 1.50 at (256, 256, 3), 2.07 at (128, 256, 3), 2.84 at
    (128, 128, 3), 5.88 on PR 33's kernel; 72 on 8 under a window of 512
    0.87 at (128, 256, 9), 1.08 at (256, 256, 3), 1.54 at (128, 256, 3),
    4.96 before; 64 heads of their own at q/k 192, v 128, 8,192 of
    8,192, 14.8 at (512, 512, 1), 17.0 at (512, 256, 1), 34.6 before.
    The same width is what an instance costs to compile: 0.55 s at
    (256, 256, 6), 0.24 at (256, 256, 3), 0.15 at (128, 256, 3) and half
    as much again at (128, 512, 3), 0.40 at (512, 512, 1), 0.23 at
    (512, 256, 1), against 0.06-0.16 before.
    So the width is bounded where PR 34 took the fastest (1,536 lanes,
    0.9 s an instance, and was refused on `setup_s`): at most 0.2 s an
    instance with grouped heads and 0.5 without."""
    if s < _LANE:
        return s, s, 1  # one tile
    sizes = [blk for blk in (512, 256, _LANE) if s % blk == 0]
    pack = max(p for p in range(1, group + 1)
               if group % p == 0 and p * _LANE <= _PREFILL_LANES)
    block_q = max(blk for blk in sizes if pack * blk <= _PREFILL_LANES)
    block_k = max(blk for blk in sizes
                  if blk <= max(block_q, _PREFILL_KEYS))
    return block_q, block_k, pack


def prefill_attention_vmem(s: int, d: int, dv: int, group: int,
                           itemsize: int = 2) -> int:
    """Bytes of VMEM a grid step of the admission kernel holds, by the
    same arithmetic as `attention_fits_vmem`: K and V of `_kv_major`
    keys and the packed heads' query and output blocks, all
    double-buffered, the scaled queries, a tile's f32 scores and
    probabilities with their cast, and the f32 output accumulator in
    scratch and as the value a tile updates."""
    block_q, block_k, pack = _pick_prefill_blocks(s, group)
    d_l, dv_l = _pad_up(d, _LANE), _pad_up(dv, _LANE)
    k_major = _kv_major(s, d, itemsize, block_k, dv, _PREFILL_KV_BUDGET)
    cols = pack * block_q
    return (2 * k_major * (d_l + dv_l) * itemsize
            + cols * (3 * d_l + 2 * dv_l) * itemsize
            + block_k * cols * (8 + itemsize)
            + 2 * dv_l * cols * 4)


@partial(jax.jit, static_argnames=("group", "window", "scale"))
def _prefill_attention_pallas(q, k, v, lengths, group: int, window,
                              scale: float):
    """q [B*H, S, D], k [B*Hkv, S, D], v [B*Hkv, S, Dv] (H = Hkv *
    group; query head b*H + h reads KV head (b*H + h) // group; Dv = D
    but for latent attention's expanded heads, 192 against 128),
    lengths [B] int32 -> [B*H, S, Dv] at q's dtype.

    The flash forward again, for serving's admission prefill, on the
    training forward's plan (`_attention_pallas`): causal, with `window`
    (static) only keys in (query - window, query], and of each batch
    row only its first `lengths[b]` positions: rows from there on are
    the bucket's padding and come back zero.  grid (B*H / pack, S /
    block_q, S / k_major): a step holds `pack` heads' query blocks and
    `k_major` keys (`_kv_major`: all of them wherever a cell's bucket
    goes, copied once a KV head because the block index does not move)
    and LOOPS over their key tiles: first those the window's trailing
    edge crosses (mask), then those wholly visible (no mask), then those
    the diagonal crosses (`_prefill_k_range`).  The running maximum, sum
    and output live in VMEM scratch and the loops carry nothing: a
    carried [Dv, lanes] f32 accumulator was a fifth to two fifths of an
    instance's compile seconds, and major blocks that stream find the
    state where the last one left it.  A tile behind the window, above
    the diagonal or past the prompt is neither started nor copied, and
    a query block past the prompt writes zeros and reads nothing.
    `lengths` rides in SMEM (scalar prefetch), so the index maps see it
    too."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    dv = v.shape[-1]
    block_q, block_k, pack = _pick_prefill_blocks(s, group)
    k_major = _kv_major(s, d, q.dtype.itemsize, block_k, dv,
                        _PREFILL_KV_BUDGET)
    n_q, n_major, n_sub = s // block_q, s // k_major, k_major // block_k
    steps_a_row = bh // lengths.shape[0] // pack   # grid rows a batch row
    cols = pack * block_q

    def q_turn(b, i, len_ref):
        """(the prompt's length, the query block grid step i takes): the
        blocks past the prompt FIRST, then its own in rising order, so
        that a head's last step is its longest, and the copy of the next
        head's K and V, which starts with that step, hides behind it."""
        length = len_ref[_div(b, steps_a_row)]
        n_own = jax.lax.min(_div(length + block_q - 1, block_q), n_q)
        return length, jax.lax.select(i < n_q - n_own, i + n_own,
                                      i - (n_q - n_own))

    def kernel(len_ref, q_ref, k_ref, v_ref, o_ref, o_acc, m_acc, l_acc):
        kj = pl.program_id(2)
        length, qi = q_turn(pl.program_id(0), pl.program_id(1), len_ref)
        r0 = qi * block_q

        @pl.when(r0 < length)
        def _own():
            bounds = _prefill_k_range(r0, jax.lax.min(r0 + block_q, length),
                                      block_k, window)
            if n_major > 1:           # this major block's share of them
                bounds = tuple(jax.lax.clamp(0, x - kj * n_sub, n_sub)
                               for x in bounds)
            t_a, t_b, t_c, t_n = bounds
            qs = _scaled(q_ref[0].reshape(cols, d), scale)
            # positions, thin: the packed heads' rows lie side by side
            # along the lanes (column n is query r0 + n % block_q), keys
            # run down the sublanes
            col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
            qpos = r0 + (jax.lax.rem(col, block_q) if pack > 1 else col)
            k_iota = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)

            @pl.when(kj == 0)
            def _init():
                o_acc[...] = jnp.zeros_like(o_acc)
                m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
                l_acc[...] = jnp.zeros_like(l_acc)

            def tile(t, masked: bool):
                c0 = pl.multiple_of(t * block_k, block_k)
                kb = k_ref[0, pl.ds(c0, block_k), :]
                vb = v_ref[0, pl.ds(c0, block_k), :]
                s_t = _dot(kb, qs, _NT)                # [bk, N] f32
                if masked:
                    s_t = _hide(s_t, qpos, kj * k_major + c0 + k_iota, True,
                                None, window)
                m_prev = m_acc[...]                    # [1, N]
                m_new = jax.lax.max(m_prev,
                                    jnp.max(s_t, axis=0, keepdims=True))
                corr = jax.lax.exp(m_prev - m_new)
                # a query a tile hides wholly (behind the window's edge)
                # has p = exp(0) there: the first tile that shows it a
                # key, and its own position always does, multiplies that
                # away (corr 0)
                p_t = jax.lax.exp(s_t - m_new)
                m_acc[...] = m_new
                l_acc[...] = l_acc[...] * corr + jnp.sum(p_t, axis=0,
                                                         keepdims=True)
                o_acc[...] = o_acc[...] * corr + _dot(
                    vb, p_t.astype(vb.dtype), _TN)     # [Dv, N]

            # the masked tiles in ONE loop (each copy of the body is a
            # fifth of an instance's compile): the window's edge's, then,
            # past the unmasked ones, the diagonal's
            _loop(t_b, t_c, lambda t, _: tile(t, False))
            _loop(t_a, t_n - (t_c - t_b), lambda u, _: tile(
                jax.lax.select(u < t_b, u, u + (t_c - t_b)), True))

            @pl.when(kj == n_major - 1)
            def _finish():
                # rows of the block past the prompt attended the
                # padding: zero
                l_fin = l_acc[...]
                inv = jax.lax.select(qpos < length, 1.0 / l_fin,
                                     jnp.zeros_like(l_fin))
                out = (o_acc[...] * inv).T.astype(o_ref.dtype)  # [N, Dv]
                for h in range(pack):
                    o_ref[0, h] = out[h * block_q:(h + 1) * block_q]

        @pl.when((r0 >= length) & (kj == n_major - 1))
        def _padding():
            o_ref[...] = jnp.zeros_like(o_ref)

    def q_block(b, i, j, len_ref):
        # blocks past the prompt park on its first one: no copy
        length, qi = q_turn(b, i, len_ref)
        return (b, 0, jax.lax.select(qi * block_q < length, qi,
                                     jnp.zeros_like(qi)), 0)

    def kv_block(b, i, j, len_ref):
        if n_major > 1:
            # major blocks behind the window, above the diagonal or past
            # the prompt park on the nearest one that shows something
            length, qi = q_turn(b, i, len_ref)
            r0 = qi * block_q
            r_end = jax.lax.min(r0 + block_q, length)
            first = 0 if window is None else _div(
                jax.lax.max(r0 - window + 1, 0), k_major)
            j = jax.lax.clamp(first, j,
                              _div(jax.lax.max(r_end - 1, 0), k_major))
        return (_div(b * pack, group), j, 0)

    o = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh // pack, pack, s, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh // pack, n_q, n_major),
            in_specs=[
                pl.BlockSpec((1, pack, block_q, d), q_block),
                pl.BlockSpec((1, k_major, d), kv_block),
                pl.BlockSpec((1, k_major, dv), kv_block),
            ],
            out_specs=pl.BlockSpec(
                (1, pack, block_q, dv),
                lambda b, i, j, len_ref: (b, 0, q_turn(b, i, len_ref)[1],
                                          0)),
            scratch_shapes=[
                pltpu.VMEM((dv, cols), jnp.float32),
                pltpu.VMEM((1, cols), jnp.float32),
                pltpu.VMEM((1, cols), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT),
        interpret=_interpret(),
    )(lengths, q.reshape(bh // pack, pack, s, d), k, v)
    return o.reshape(bh, s, dv)


def _xla_prefill_attention(q, k, v, window, lengths=None):
    """Dense composition of the same: [B, S, H|Hkv, D|Dv] -> [B, S, H,
    Dv] f32, rows from `lengths` [B] on zero."""
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
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    if lengths is None:
        return o
    own = jnp.arange(s)[None] < lengths[:, None]
    return jnp.where(own[:, :, None, None], o, 0.0)


def _prefill_shape_ok(s: int, group: int, d: int, dv: int,
                      itemsize: int = 2) -> bool:
    """A q/k head of whole or one and a half lane tiles (128, 192, 256:
    the contraction compiles at the 64-minor tile), a lane-wide v head,
    a sequence the blocks tile, and a grid step within the VMEM asked."""
    return (d >= _LANE and d % 64 == 0 and dv % _LANE == 0 and s % 8 == 0
            and (s <= _LANE or s % _LANE == 0)
            and prefill_attention_vmem(s, d, dv, group, itemsize)
            <= _PREFILL_VMEM_BUDGET)


def prefill_attention_ok(q, v=None) -> bool:
    """q [B, S, H, D], v [B, S, Hkv, Dv] (None: Dv = D): whether the
    admission kernel takes the shape (`_prefill_shape_ok`)."""
    _b, s, h, d = q.shape
    dv, hkv = (d, h) if v is None else (v.shape[-1], v.shape[2])
    return _prefill_shape_ok(s, h // hkv, d, dv, q.dtype.itemsize)


def prefill_tile_counts(s: int, length: int, window, group: int, d: int,
                        dv: int, itemsize: int = 2) -> tuple:
    """(own, bucket): the score tiles the admission kernel visits, a
    grid row (the query heads packed into one step), for a row of
    `length` own positions in a bucket of s, and for a row that fills
    the bucket: the key tiles of `_prefill_k_range` a query block, as
    the kernel loops over them.
    (0, 0) at a shape the kernel declines (`_prefill_shape_ok`), where
    the XLA composition runs and visits no tile.  What the batcher adds
    up a request."""
    if not _prefill_shape_ok(s, group, d, dv, itemsize):
        return 0, 0
    block_q, block_k, _pack = _pick_prefill_blocks(s, group)

    def tiles(length):
        count = 0
        for r0 in range(0, min(length, s), block_q):
            a, _b, _c, n = _prefill_k_range(r0, min(r0 + block_q, length),
                                            block_k, window)
            count += n - a
        return count

    return tiles(length), tiles(s)


def prefill_attention(q, k, v, window=None, lengths=None,
                      kernel: bool = True):
    """Causal attention of a whole prompt with grouped heads and an
    optional window: q [B, S, H, D], k [B, S, Hkv, D], v [B, S, Hkv, Dv]
    -> [B, S, H, Dv] at q's dtype.  `lengths` [B] int32: the count of a
    row's own positions in the bucket (None: all S); the rows from there
    on come back zero, on both arms, and cost the kernel nothing.
    `kernel` False (or a shape the kernel declines) takes the XLA
    composition."""
    b, s, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    if not (kernel and prefill_attention_ok(q, v)):
        return _xla_prefill_attention(q, k, v, window,
                                      lengths).astype(q.dtype)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    o = _prefill_attention_pallas(
        _to_bhsd(q, d), _to_bhsd(k, d), _to_bhsd(v, dv),
        lengths.astype(jnp.int32), group=h // hkv, window=window,
        scale=1.0 / float(d) ** 0.5)
    return _from_bhsd(o, b, s, h, dv)
