"""Dropless expert dispatch and the grouped matmul over the experts held.

A routed-expert layer sends every token to its top-k experts; this chip
holds `E` of them.  `dispatch` sorts the (token, expert) assignments that
fall on the experts held into one row buffer in which every expert's rows
start on a tile boundary, so a row tile belongs to exactly ONE expert and
the grouped matmul is a plain tiled matmul whose weight block is chosen
per row tile:

    rows  [M, K]   M = (ceil(T*k / tm) + E) * tm   (static worst case:
                   every assignment held, every expert's last tile ragged)
    tile_expert [M / tm]   the expert of each row tile; tiles past the
                   used ones repeat the last used expert
    n_tiles [1]            row tiles in use

No capacity, no drops: an expert that draws every token of the batch gets
them all (its rows fill consecutive tiles).

The kernel (`_gmm_call`): grid (N / tn, M / tm), row tiles innermost, the
weight block `(tile_expert[t], :, n)` picked through scalar prefetch.
Consecutive tiles of one expert map to the same block and Mosaic skips
the copy, so every expert touched is read ONCE per column tile whatever
its row count: decode (one or two rows an expert, tm = 16) is bound by the
touched experts' bytes, prefill (hundreds of rows an expert, tm = 128) by
the MXU.  Tiles past `n_tiles` park on the last used blocks and compute
nothing.  K is taken whole in a block (3,072 x 512 bf16 = 3 MB), products
accumulate in float32.

`expert_mlp` is the layer's routed part: gate and up projections in one
call with the SiLU gate as epilogue, the down projection in a second,
under a named jitted wrapper: `_moe_gmm_decode` at the decode-sized row
tile (bound by the touched experts' bytes), `_moe_gmm_prefill` at the
MXU's (bound by FLOPs).  The names are what the benchmark's
`moe_expert_ms` (`^_moe_gmm`) and its two per-regime metrics find in the
trace.  Off the TPU the same row buffer goes through `jax.lax.ragged_dot`
(same numbers: bf16 operands, f32 accumulation); tests/test_moe_lm.py
holds the kernel in interpret mode to it.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .pallas_kernels import _interpret

__all__ = ["dispatch", "expert_mlp", "combine", "Plan", "row_tile"]

_LANE = 128
_DECODE_TILE = 16                    # a bf16 sublane tile
_W_BLOCK_BYTES = 3 * 1024 * 1024     # a weight block of one grid step
_VMEM_LIMIT = 48 * 1024 * 1024       # of the v5e's 128 MiB


class Plan(NamedTuple):
    """Where each assignment's row lives, and what each row tile holds."""
    src: jax.Array          # [M] token index of each buffer row
    dest: jax.Array         # [T, k] buffer row of each assignment (0: none)
    held: jax.Array         # [T, k] bool: the assignment fell on an expert held
    tile_expert: jax.Array  # [M / tm] local expert id of each row tile
    n_tiles: jax.Array      # [1] row tiles in use
    group_sizes: jax.Array  # [E] padded rows of each expert (ragged_dot)
    counts: jax.Array       # [E] assignments that fell on each expert


def row_tile(rows: int) -> int:
    """Row-tile height for `rows` tokens a call: a bf16 sublane tile for
    decode-sized calls (an expert sees one or two rows), the MXU's 128
    where experts see many."""
    return _DECODE_TILE if rows <= 64 else _LANE


def dispatch(expert_ids, lo: int, hi: int, tm: int) -> Plan:
    """expert_ids [T, k] int32 over ALL experts; this chip holds
    [lo, hi).  Pure index arithmetic, no token data."""
    t, k = expert_ids.shape
    e_held = hi - lo
    a = t * k
    n_tiles_max = -(-a // tm) + e_held
    m = n_tiles_max * tm
    flat = expert_ids.reshape(a)
    held = (flat >= lo) & (flat < hi)
    e = jnp.where(held, flat - lo, e_held)              # sentinel sorts last
    order = jnp.argsort(e, stable=True)
    counts = jnp.zeros(e_held + 1, jnp.int32).at[e].add(1)[:e_held]
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    first = jnp.cumsum(counts) - counts                 # rank base, sorted
    e_sorted = e[order]
    e_clip = jnp.minimum(e_sorted, e_held - 1)
    rank = jnp.arange(a, dtype=jnp.int32) - first[e_clip]
    dest_sorted = jnp.where(e_sorted < e_held, starts[e_clip] + rank, m)
    src = jnp.zeros(m, jnp.int32).at[dest_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    dest = jnp.zeros(a, jnp.int32).at[order].set(
        jnp.where(dest_sorted < m, dest_sorted, 0).astype(jnp.int32))
    n_tiles = (ends[-1] // tm).astype(jnp.int32)
    tile_row = jnp.arange(n_tiles_max, dtype=jnp.int32) * tm
    tile_row = jnp.minimum(tile_row, jnp.maximum(ends[-1] - tm, 0))
    tile_expert = jnp.minimum(
        jnp.sum(ends[None, :] <= tile_row[:, None], axis=1),
        e_held - 1).astype(jnp.int32)
    return Plan(src, dest.reshape(t, k), held.reshape(t, k), tile_expert,
                n_tiles.reshape(1), padded, counts)


def _col_tile(k: int, n: int, weights: int) -> int:
    """Widest 128-multiple column tile dividing n whose `weights` blocks
    of [k, tn] bf16 stay inside the per-step budget."""
    best = None
    for tn in range(_LANE, n + 1, _LANE):
        if n % tn == 0 and weights * k * tn * 2 <= 2 * _W_BLOCK_BYTES:
            best = tn
    return best if best is not None else (n if n % _LANE else _LANE)


def _tile_dot(x, w_ref):
    """One row tile x [tm, K] through the weight block `w_ref`
    (1, K, tn) of its expert: products accumulate in float32."""
    return jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)


def _gmm_call(x, ws, tile_expert, n_tiles, tm: int, gated: bool, out_dtype):
    """rows x [M, K] (bf16) through weights ws ([E, K, N] each) chosen per
    row tile.  gated: two weights, result silu(x w0) * (x w1)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    _, _, n = ws[0].shape
    tn = _col_tile(k, n, len(ws))
    t_max = m // tm

    def kernel(te_ref, nt_ref, x_ref, *refs):
        w_refs, o_ref = refs[:-1], refs[-1]
        t = pl.program_id(1)

        @pl.when(t < nt_ref[0])
        def _tile():
            xb = x_ref[...]
            ys = [_tile_dot(xb, w) for w in w_refs]
            y = jax.nn.silu(ys[0]) * ys[1] if gated else ys[0]
            o_ref[...] = y.astype(o_ref.dtype)

    def last(t, nt):
        return jnp.minimum(t, jnp.maximum(nt[0] - 1, 0))

    x_spec = pl.BlockSpec((tm, k), lambda j, t, te, nt: (last(t, nt), 0))
    w_spec = pl.BlockSpec((1, k, tn), lambda j, t, te, nt: (te[t], 0, j))
    o_spec = pl.BlockSpec((tm, tn), lambda j, t, te, nt: (last(t, nt), j))
    params = {}
    if not _interpret():
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, t_max),
            in_specs=[x_spec] + [w_spec] * len(ws), out_specs=o_spec),
        interpret=_interpret(), **params,
    )(tile_expert, n_tiles, x, *ws)


def _moe_gmm(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm: int):
    h = _gmm_call(rows, (w_gate, w_up), tile_expert, n_tiles, tm, True,
                  rows.dtype)
    return _gmm_call(h, (w_down,), tile_expert, n_tiles, tm, False,
                     rows.dtype)


# A jitted wrapper's name is its device events' name: `^_moe_gmm` finds
# both calls of both regimes, the whole names one regime.
@partial(jax.jit, static_argnames=("tm",))
def _moe_gmm_decode(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm):
    return _moe_gmm(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm)


@partial(jax.jit, static_argnames=("tm",))
def _moe_gmm_prefill(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm):
    return _moe_gmm(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm)


def _moe_ragged(rows, w_gate, w_up, w_down, group_sizes):
    """The same row buffer through `jax.lax.ragged_dot`: rows past the
    groups come out zero."""
    def rd(a, w):
        return jax.lax.ragged_dot(a, w, group_sizes,
                                  preferred_element_type=jnp.float32)

    h = (jax.nn.silu(rd(rows, w_gate)) * rd(rows, w_up)).astype(rows.dtype)
    return rd(h, w_down).astype(rows.dtype)


def expert_mlp(x, plan: Plan, w_gate, w_up, w_down, tm: int, kernel: bool):
    """x [T, K] -> the experts' outputs for every buffer row [M, K]:
    E_e(x[src]) with E the gated MLP of the row's expert."""
    rows = x[plan.src]
    if kernel:
        wrapper = _moe_gmm_decode if tm == _DECODE_TILE else _moe_gmm_prefill
        return wrapper(rows, w_gate, w_up, w_down, plan.tile_expert,
                       plan.n_tiles, tm=tm)
    return _moe_ragged(rows, w_gate, w_up, w_down, plan.group_sizes)


def combine(y_rows, plan: Plan, weights):
    """sum_k weights[t, k] * y_rows[dest[t, k]] over the assignments held
    -> [T, N] float32."""
    # rows no tile computed are never written: mask the rows, not only
    # their weights (0 x garbage is not 0)
    y = jnp.where(plan.held[..., None],
                  y_rows[plan.dest].astype(jnp.float32), 0.0)
    return jnp.einsum("tk,tkn->tn", weights.astype(jnp.float32), y)
