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

TRAINING (`expert_mlp(..., train=True)`).  Off the TPU `ragged_dot`
differentiates by itself.  On it the two calls sit under a `custom_vjp`
(`_moe_gmm_train`), forward `_moe_gmm_train_fwd`, whose backward is two
more named programs over the same `Plan`:

    _moe_gmm_bwd_dx   one kernel RECOMPUTES the gate's inputs from the
                      rows (g = x Wg, u = x Wu, in float32 in VMEM) beside
                      dh = dy Wd^T and writes dg, du and h = silu(g) u;
                      then drows = dg Wg^T + du Wu^T, the forward's tiled
                      product against the TRANSPOSED weight blocks
    _moe_gmm_bwd_dw   dW_e = rows_e^T dy_e for Wg, Wu, Wd: an expert's row
                      tiles are consecutive, so its [K, tn] float32
                      accumulator stays in VMEM until the expert changes
                      and is written once; an expert that drew no row is
                      never visited and reads zero (masked by its count);
                      tiles past `n_tiles` compute nothing

Recomputing rather than saving g and u: 2 x M x N bf16 a layer not kept
(2 x 33,792 x 1,536 x 2 B = 208 MB at 8,192 tokens of top-4) for
4 K N FLOPs a USED row more.  Rows past the used tiles are never written
by any kernel: `_moe_gmm_bwd_dx` zeroes their drows, because the gather's
transpose adds every buffer row to SOME token.

`gather` (rows = x[src]) and `combine` are named jitted programs too
(`_moe_rows_gather`, `_moe_rows_combine`; `^_moe_rows_` in a trace is what
the dropless buffers cost) and carry their own transposes, written as
gathers over the plan (a buffer row belongs to one assignment, an
assignment to one row) where autodiff would scatter-add.  On a v5e at
16,384 tokens of top-4 into 66,560 rows of 2,048, forward and backward
together: gather 4.94 ms against 6.13 with autodiff's transpose, combine
9.84 against 11.64, equal results; 15 ms a step of five routed layers
(PERF.md section 6, PR 33's review round).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .pallas_kernels import _interpret

__all__ = ["dispatch", "expert_mlp", "gather", "combine", "Plan", "row_tile"]

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
    assign: jax.Array       # [M] flat assignment (t * k + j) of a row, -1: none


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
    assign = jnp.full(m, -1, jnp.int32).at[dest_sorted].set(
        order.astype(jnp.int32), mode="drop")
    src = jnp.maximum(assign, 0) // k
    dest = jnp.zeros(a, jnp.int32).at[order].set(
        jnp.where(dest_sorted < m, dest_sorted, 0).astype(jnp.int32))
    n_tiles = (ends[-1] // tm).astype(jnp.int32)
    tile_row = jnp.arange(n_tiles_max, dtype=jnp.int32) * tm
    tile_row = jnp.minimum(tile_row, jnp.maximum(ends[-1] - tm, 0))
    tile_expert = jnp.minimum(
        jnp.sum(ends[None, :] <= tile_row[:, None], axis=1),
        e_held - 1).astype(jnp.int32)
    return Plan(src, dest.reshape(t, k), held.reshape(t, k), tile_expert,
                n_tiles.reshape(1), padded, counts, assign)


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


def _tile_dot_t(x, w_ref):
    """x [tm, N] against the TRANSPOSED block `w_ref` (1, tk, N) of its
    expert's [K, N] weight: x w^T -> [tm, tk] float32."""
    return jax.lax.dot_general(x, w_ref[0], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _last(t, nt):
    """The row tile a grid step parks on: its own while in use."""
    return jnp.minimum(t, jnp.maximum(nt[0] - 1, 0))


def _compiler_params(pltpu) -> dict:
    if _interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}


def _gmm_call(xs, ws, tile_expert, n_tiles, tm: int, gated: bool, out_dtype,
              transposed: bool = False):
    """Row buffers xs ([M, K] bf16 each) through weights ws chosen per
    row tile.  One buffer: every weight reads it, and `gated` (two
    weights) gives silu(x w0) * (x w1).  A buffer a weight: the products
    are summed.  ws are [E, K, N], or `transposed` [E, N, K] read as
    their transposes (the backward's products)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = xs[0].shape
    n = ws[0].shape[1 if transposed else 2]
    tn = _col_tile(k, n, len(ws))
    t_max = m // tm
    dot = _tile_dot_t if transposed else _tile_dot

    def kernel(te_ref, nt_ref, *refs):
        x_refs, w_refs, o_ref = (refs[:len(xs)], refs[len(xs):-1], refs[-1])
        t = pl.program_id(1)

        @pl.when(t < nt_ref[0])
        def _tile():
            rows = [x[...] for x in x_refs] * (len(ws) // len(xs))
            ys = [dot(xb, w) for xb, w in zip(rows, w_refs)]
            y = jax.nn.silu(ys[0]) * ys[1] if gated else sum(ys[1:], ys[0])
            o_ref[...] = y.astype(o_ref.dtype)

    x_spec = pl.BlockSpec((tm, k), lambda j, t, te, nt: (_last(t, nt), 0))
    if transposed:
        w_spec = pl.BlockSpec((1, tn, k), lambda j, t, te, nt: (te[t], j, 0))
    else:
        w_spec = pl.BlockSpec((1, k, tn), lambda j, t, te, nt: (te[t], 0, j))
    o_spec = pl.BlockSpec((tm, tn), lambda j, t, te, nt: (_last(t, nt), j))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, t_max),
            in_specs=[x_spec] * len(xs) + [w_spec] * len(ws),
            out_specs=o_spec),
        interpret=_interpret(), **_compiler_params(pltpu),
    )(tile_expert, n_tiles, *xs, *ws)


def _moe_gmm(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm: int):
    h = _gmm_call((rows,), (w_gate, w_up), tile_expert, n_tiles, tm, True,
                  rows.dtype)
    return _gmm_call((h,), (w_down,), tile_expert, n_tiles, tm, False,
                     rows.dtype)


# A jitted wrapper's name is its device events' name: `^_moe_gmm` finds
# both calls of both regimes, the whole names one regime.
@partial(jax.jit, static_argnames=("tm",))
def _moe_gmm_decode(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm):
    return _moe_gmm(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm)


@partial(jax.jit, static_argnames=("tm",))
def _moe_gmm_prefill(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm):
    return _moe_gmm(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm)


# ---- training: the forward again, and its transpose -----------------------
@partial(jax.jit, static_argnames=("tm",))
def _moe_gmm_train_fwd(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm):
    return _moe_gmm(rows, w_gate, w_up, w_down, tile_expert, n_tiles, tm)


def _gate_bwd_call(x, dy, w_gate, w_up, w_down, tile_expert, n_tiles,
                   tm: int):
    """-> (dg, du, h), [M, N] each at x's dtype: the gate's inputs
    g = x Wg and u = x Wu recomputed a tile in VMEM beside dh = dy Wd^T,
    then dg = dh u silu'(g), du = dh silu(g) and h = silu(g) u as the
    forward rounded it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = w_gate.shape[2]
    tn = _col_tile(k, n, 3)
    t_max = m // tm

    def kernel(te_ref, nt_ref, x_ref, dy_ref, wg_ref, wu_ref, wd_ref,
               dg_ref, du_ref, h_ref):
        t = pl.program_id(1)

        @pl.when(t < nt_ref[0])
        def _tile():
            xb = x_ref[...]
            g, u = _tile_dot(xb, wg_ref), _tile_dot(xb, wu_ref)
            dh = _tile_dot_t(dy_ref[...], wd_ref)
            sig = jax.nn.sigmoid(g)
            act = g * sig
            h_ref[...] = (act * u).astype(h_ref.dtype)
            du_ref[...] = (dh * act).astype(du_ref.dtype)
            dg_ref[...] = (dh * u * (sig * (1.0 + g * (1.0 - sig)))).astype(
                dg_ref.dtype)

    row = lambda j, t, te, nt: (_last(t, nt), 0)            # noqa: E731
    col = lambda j, t, te, nt: (_last(t, nt), j)            # noqa: E731
    w_spec = pl.BlockSpec((1, k, tn), lambda j, t, te, nt: (te[t], 0, j))
    wd_spec = pl.BlockSpec((1, tn, k), lambda j, t, te, nt: (te[t], j, 0))
    out = jax.ShapeDtypeStruct((m, n), x.dtype)
    return pl.pallas_call(
        kernel, out_shape=(out, out, out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, t_max),
            in_specs=[pl.BlockSpec((tm, k), row), pl.BlockSpec((tm, k), row),
                      w_spec, w_spec, wd_spec],
            out_specs=[pl.BlockSpec((tm, tn), col)] * 3),
        interpret=_interpret(), **_compiler_params(pltpu),
    )(tile_expert, n_tiles, x, dy, w_gate, w_up, w_down)


def _gmm_dw_call(x, dy, tile_expert, n_tiles, counts, tm: int, out_dtype):
    """dW [E, K, N]: dW_e = x_e^T dy_e over expert e's rows (x [M, K],
    dy [M, N]).  Grid (N / tn, M / tm), row tiles innermost: an expert's
    tiles are consecutive, its accumulator is zeroed at its first tile
    and written at its last.  Experts without rows are never visited:
    `counts` zeroes them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = dy.shape[1]
    e_held = counts.shape[0]
    tn = _col_tile(k, n, 2)         # the f32 accumulator is two bf16 blocks
    t_max = m // tm

    def kernel(te_ref, nt_ref, x_ref, dy_ref, o_ref, acc_ref):
        t = pl.program_id(1)
        nt = nt_ref[0]

        @pl.when(t < nt)
        def _tile():
            e = te_ref[t]
            first = jnp.logical_or(
                t == 0, te_ref[jnp.maximum(t - 1, 0)] != e)
            last = jnp.logical_or(
                t == nt - 1, te_ref[jnp.minimum(t + 1, t_max - 1)] != e)
            prod = jax.lax.dot_general(
                x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

            @pl.when(first)
            def _start():
                acc_ref[...] = prod

            @pl.when(jnp.logical_not(first))
            def _add():
                acc_ref[...] += prod

            @pl.when(last)
            def _write():
                o_ref[0] = acc_ref[...].astype(o_ref.dtype)

    dw = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((e_held, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, t_max),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, t, te, nt: (_last(t, nt), 0)),
                pl.BlockSpec((tm, tn), lambda j, t, te, nt: (_last(t, nt), j))],
            out_specs=pl.BlockSpec((1, k, tn),
                                   lambda j, t, te, nt: (te[t], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
        interpret=_interpret(), **_compiler_params(pltpu),
    )(tile_expert, n_tiles, x, dy)
    return jnp.where((counts > 0)[:, None, None], dw, jnp.zeros((), out_dtype))


@partial(jax.jit, static_argnames=("tm",))
def _moe_gmm_bwd_dx(rows, dy, w_gate, w_up, w_down, tile_expert, n_tiles, tm):
    """-> (drows, dg, du, h).  drows of the rows no tile computed is 0."""
    dg, du, h = _gate_bwd_call(rows, dy, w_gate, w_up, w_down, tile_expert,
                               n_tiles, tm)
    drows = _gmm_call((dg, du), (w_gate, w_up), tile_expert, n_tiles, tm,
                      False, rows.dtype, transposed=True)
    used = jnp.arange(rows.shape[0]) < n_tiles[0] * tm
    return jnp.where(used[:, None], drows, 0), dg, du, h


@partial(jax.jit, static_argnames=("tm",))
def _moe_gmm_bwd_dw(rows, dg, du, h, dy, tile_expert, n_tiles, counts, tm):
    def dw(x, g):
        return _gmm_dw_call(x, g, tile_expert, n_tiles, counts, tm,
                            rows.dtype)

    return dw(rows, dg), dw(rows, du), dw(h, dy)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def _moe_gmm_train(rows, w_gate, w_up, w_down, tile_expert, n_tiles, counts,
                   tm):
    return _moe_gmm_train_f(rows, w_gate, w_up, w_down, tile_expert,
                            n_tiles, counts, tm)[0]


def _moe_gmm_train_f(rows, w_gate, w_up, w_down, tile_expert, n_tiles,
                     counts, tm):
    y = _moe_gmm_train_fwd(rows, w_gate, w_up, w_down, tile_expert, n_tiles,
                           tm=tm)
    return y, (rows, w_gate, w_up, w_down, tile_expert, n_tiles, counts)


def _moe_gmm_train_b(tm, res, dy):
    rows, w_gate, w_up, w_down, tile_expert, n_tiles, counts = res
    drows, dg, du, h = _moe_gmm_bwd_dx(rows, dy, w_gate, w_up, w_down,
                                       tile_expert, n_tiles, tm=tm)
    dwg, dwu, dwd = _moe_gmm_bwd_dw(rows, dg, du, h, dy, tile_expert,
                                    n_tiles, counts, tm=tm)
    return (drows, dwg.astype(w_gate.dtype), dwu.astype(w_up.dtype),
            dwd.astype(w_down.dtype), None, None, None)


_moe_gmm_train.defvjp(_moe_gmm_train_f, _moe_gmm_train_b)


def _moe_ragged(rows, w_gate, w_up, w_down, group_sizes):
    """The same row buffer through `jax.lax.ragged_dot`: rows past the
    groups come out zero."""
    def rd(a, w):
        return jax.lax.ragged_dot(a, w, group_sizes,
                                  preferred_element_type=jnp.float32)

    h = (jax.nn.silu(rd(rows, w_gate)) * rd(rows, w_up)).astype(rows.dtype)
    return rd(h, w_down).astype(rows.dtype)


# ---- the row buffer: in and out -------------------------------------------
@jax.jit
def _moe_rows_gather(x, src):
    return x[src]


@jax.jit
def _moe_rows_gather_bwd(drows, dest, held, like):
    """Transpose of rows = x[src] as a gather: a token's gradient is the
    sum of its held assignments' rows."""
    d = jnp.where(held[..., None], drows[dest].astype(jnp.float32), 0.0)
    return jnp.sum(d, 1).astype(like.dtype)


@jax.custom_vjp
def _gather(x, src, dest, held):
    return _moe_rows_gather(x, src)


_gather.defvjp(
    lambda x, src, dest, held: (_moe_rows_gather(x, src),
                                (dest, held, jnp.zeros((), x.dtype))),
    lambda res, drows: (_moe_rows_gather_bwd(drows, *res), None, None, None))


def gather(x, plan: Plan):
    """x [T, K] -> the row buffer [M, K]: each assignment held gets its
    token's row; rows that hold none repeat token 0's."""
    return _gather(x, plan.src, plan.dest, plan.held)


def expert_mlp(x, plan: Plan, w_gate, w_up, w_down, tm: int, kernel: bool,
               train: bool = False):
    """x [T, K] -> the experts' outputs for every buffer row [M, K]:
    E_e(x[src]) with E the gated MLP of the row's expert.  `train`: the
    kernel arm that carries a backward (`ragged_dot` has its own)."""
    rows = gather(x, plan)
    if kernel and train:
        return _moe_gmm_train(rows, w_gate, w_up, w_down, plan.tile_expert,
                              plan.n_tiles, plan.counts, tm)
    if kernel:
        wrapper = _moe_gmm_decode if tm == _DECODE_TILE else _moe_gmm_prefill
        return wrapper(rows, w_gate, w_up, w_down, plan.tile_expert,
                       plan.n_tiles, tm=tm)
    return _moe_ragged(rows, w_gate, w_up, w_down, plan.group_sizes)


@jax.jit
def _moe_rows_combine(y_rows, weights, dest, held):
    # rows no tile computed are never written: mask the rows, not only
    # their weights (0 x garbage is not 0)
    y = jnp.where(held[..., None], y_rows[dest].astype(jnp.float32), 0.0)
    return jnp.einsum("tk,tkn->tn", weights.astype(jnp.float32), y)


@jax.jit
def _moe_rows_combine_bwd(g, y_rows, weights, dest, held, src, assign):
    """Transposes of combine, as gathers: a buffer row's gradient is its
    one assignment's weight times its token's; a weight's is its row
    against its token's gradient."""
    w_row = jnp.where(assign >= 0,
                      weights.reshape(-1)[jnp.maximum(assign, 0)], 0.0)
    dy = (w_row[:, None].astype(jnp.float32) * g[src]).astype(y_rows.dtype)
    y = jnp.where(held[..., None], y_rows[dest].astype(jnp.float32), 0.0)
    dw = jnp.einsum("tkn,tn->tk", y, g).astype(weights.dtype)
    return dy, dw


@jax.custom_vjp
def _combine(y_rows, weights, dest, held, src, assign):
    return _moe_rows_combine(y_rows, weights, dest, held)


_combine.defvjp(
    lambda y_rows, weights, dest, held, src, assign: (
        _moe_rows_combine(y_rows, weights, dest, held),
        (y_rows, weights, dest, held, src, assign)),
    lambda res, g: _moe_rows_combine_bwd(g, *res) + (None,) * 4)


def combine(y_rows, plan: Plan, weights):
    """sum_k weights[t, k] * y_rows[dest[t, k]] over the assignments held
    -> [T, N] float32."""
    return _combine(y_rows, weights, plan.dest, plan.held, plan.src,
                    plan.assign)
