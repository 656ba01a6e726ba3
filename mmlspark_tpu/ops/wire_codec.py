"""Compressed-wire codec: RLE-encoded pixels decoded ON DEVICE.

Where the host-to-device link is the bound, the cheapest byte is the one
never sent.  Classification pixels are highly
runnable — letterboxed borders, flat backgrounds, uint8 quantization —
so the feed's compressed path ships a byte-level run-length encoding of
each chunk (values + a cumulative-length table) and expands it back into
the raw uint8 buffer on the chip:

  * **Wire format.**  `rle_encode` walks the chunk's raw bytes into
    (value, run) pairs with runs capped at 255 (a worst-case incompres-
    sible buffer costs 5 bytes per byte-run; real image batches measure
    2-20x smaller — `rle_ratio` reports per chunk, and the feed only
    takes this path when the ratio clears `MIN_WIRE_RATIO`).  The wire
    carries `values` (uint8[R]) and the cumulative `ends` table
    (int32[R]); both are padded to a power-of-two run count so the
    on-device decode program caches by (R, N) signature instead of
    recompiling per batch.
  * **XLA decode (every backend).**  `jnp.repeat(values, counts,
    total_repeat_length=N)` — counts recovered on device by differencing
    `ends`.  This is the transparent-fallback rung: it runs anywhere,
    so a backend without Pallas still gets the wire savings.
  * **Pallas page-walk decode (TPU).**  The paged-KV kernel
    (ops/paged_attention.py) proved the pattern: a scalar-prefetched
    table drives each grid step's BlockSpec index map, so every output
    block DMAs exactly the slab it needs.  Here the prefetched table is
    `first_run[p]` — the index of the run containing output position
    p*B, built host-side by one searchsorted over `ends` — and each
    output block walks two adjacent W-run windows of (values, ends)
    whose base indices come straight from that table.  Two windows
    because a B-byte block can span at most B runs starting anywhere
    inside a window: with B == W the pair always covers it.  Selected
    by `rle_kernel_ok()` (TPU backend, or forced via
    MMLSPARK_RLE_KERNEL=1 for interpret-mode tests on CPU).

See docs/performance.md ("The sharded and compressed feed paths") and
the guide at /opt/skills/guides/pallas_guide.md for the scalar-prefetch
idiom.
"""
from __future__ import annotations

import os
from functools import lru_cache
from typing import Any, Tuple

import numpy as np

__all__ = ["RLEPayload", "rle_encode", "rle_ratio", "rle_kernel_ok",
           "decode_bytes", "decode_host", "MIN_WIRE_RATIO", "RUN_CAP",
           "BLOCK"]

RUN_CAP = 255        # max run length per entry (worst case 5 bytes/run wire)
BLOCK = 128          # output bytes per grid step == runs per window (B == W)
MIN_WIRE_RATIO = 1.5  # feed takes the compressed path only above this


class RLEPayload:
    """One host chunk, RLE-encoded for the wire.

    `values`/`ends` are the padded wire arrays (uint8[R], int32[R], R a
    power of two >= 2*BLOCK); `first_run` is the scalar-prefetch table
    for the Pallas decode; `shape`/`dtype` restore the chunk; `n_pad`
    is the padded decoded byte length (multiple of BLOCK)."""

    __slots__ = ("values", "ends", "first_run", "shape", "dtype",
                 "nbytes_raw", "n_pad")

    def __init__(self, values: np.ndarray, ends: np.ndarray,
                 first_run: np.ndarray, shape: Tuple[int, ...],
                 dtype: np.dtype, nbytes_raw: int, n_pad: int):
        self.values = values
        self.ends = ends
        self.first_run = first_run
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.nbytes_raw = int(nbytes_raw)
        self.n_pad = int(n_pad)

    @property
    def wire_nbytes(self) -> int:
        return int(self.values.nbytes + self.ends.nbytes)


def _pow2_at_least(n: int, floor: int) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


def rle_encode(arr: np.ndarray) -> RLEPayload:
    """Byte-level RLE of `arr`'s raw buffer, runs capped at RUN_CAP.

    Vectorized: change points via one diff over the byte view, then the
    cap splits long runs arithmetically — no Python-per-byte loop."""
    arr = np.ascontiguousarray(arr)
    raw = arr.reshape(-1).view(np.uint8)
    n = raw.size
    if n == 0:
        raise ValueError("cannot RLE-encode an empty array")
    # run boundaries: index i starts a run iff raw[i] != raw[i-1]
    starts = np.flatnonzero(np.concatenate(
        ([True], raw[1:] != raw[:-1])))
    lengths = np.diff(np.concatenate((starts, [n]))).astype(np.int64)
    vals = raw[starts]
    # split runs longer than RUN_CAP into ceil(len/cap) capped pieces
    pieces = -(-lengths // RUN_CAP)
    values = np.repeat(vals, pieces)
    counts = np.full(values.size, RUN_CAP, np.int64)
    # last piece of each run carries the remainder
    last = np.cumsum(pieces) - 1
    rem = lengths - (pieces - 1) * RUN_CAP
    counts[last] = rem
    ends = np.cumsum(counts)
    # pad the decoded length to a BLOCK multiple with one final pad run,
    # then pad the run count to a power of two with zero-length runs so
    # the decode program caches by (R, N) instead of recompiling
    n_pad = -(-n // BLOCK) * BLOCK
    # always append a terminal pad run ending at n_pad: padded output
    # positions must resolve to SOME run, and it also absorbs the
    # BLOCK-rounding slack when n is not a multiple of BLOCK
    ends = np.concatenate((ends, np.array([n_pad], np.int64)))
    values = np.concatenate((values, np.array([0], np.uint8)))
    r_pad = _pow2_at_least(ends.size, 2 * BLOCK)
    ends_p = np.full(r_pad, n_pad, np.int32)
    ends_p[:ends.size] = ends
    vals_p = np.zeros(r_pad, np.uint8)
    vals_p[:values.size] = values
    nb = n_pad // BLOCK
    first_run = np.searchsorted(
        ends_p, np.arange(nb, dtype=np.int64) * BLOCK, side="right"
    ).astype(np.int32)
    return RLEPayload(vals_p, ends_p, first_run, arr.shape, arr.dtype,
                      n, n_pad)


def decode_host(payload: RLEPayload) -> np.ndarray:
    """Host-side decode (the degraded-feed fallback: raw bytes back on
    the host, then a plain put)."""
    counts = np.diff(payload.ends.astype(np.int64), prepend=0)
    raw = np.repeat(payload.values, counts)
    return (raw[:payload.nbytes_raw].view(payload.dtype)
            .reshape(payload.shape))


def rle_ratio(payload: RLEPayload) -> float:
    """Raw bytes per wire byte — the compression the wire would see."""
    return payload.nbytes_raw / max(1, payload.wire_nbytes)


def rle_kernel_ok() -> bool:
    """Route decode through the Pallas page-walk kernel?  TPU only by
    default (the XLA repeat path is faster through CPU interpret mode);
    MMLSPARK_RLE_KERNEL=1 forces it so tier-1 tests exercise the kernel
    in interpret mode, MMLSPARK_NO_RLE_KERNEL wins over both."""
    if os.environ.get("MMLSPARK_NO_RLE_KERNEL"):
        return False
    if os.environ.get("MMLSPARK_RLE_KERNEL"):
        return True
    from .pallas_kernels import on_tpu

    return on_tpu()


# ---------------------------------------------------------------------------
# decode programs, cached per (R, N) signature
# ---------------------------------------------------------------------------
@lru_cache(maxsize=64)
def _xla_decode(r: int, n_pad: int):
    import jax
    import jax.numpy as jnp

    def decode(values, ends):
        counts = jnp.diff(ends, prepend=0)
        return jnp.repeat(values, counts, total_repeat_length=n_pad)

    return jax.jit(decode)


@lru_cache(maxsize=64)
def _pallas_decode(r: int, n_pad: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .pallas_kernels import _interpret

    w = BLOCK
    nw = r // w          # run windows (r is a pow2 >= 2*BLOCK)
    nb = n_pad // BLOCK  # output blocks

    # Mosaic layout: the run tables enter as COLUMNS ([nw, w, 1] blocks
    # of (w, 1)) and each output block leaves as a ROW ((1, BLOCK)), so
    # runs lie along sublanes and output positions along lanes — every
    # intermediate is a plain 2D [2w, BLOCK] tile, the two reductions
    # run over sublanes, and nothing has to be transposed in the kernel
    # (a (1, w) block of an [nw, w] table is not a legal TPU block).
    def kernel(fr_ref, v0_ref, v1_ref, e0_ref, e1_ref, o_ref):
        p = pl.program_id(0)
        w0 = fr_ref[p] // w
        # second window duplicates the first when clamped at the table's
        # edge — mask its contribution instead of double-counting
        dup = (jnp.minimum(w0 + 1, nw - 1) == w0)
        ends = jnp.concatenate([e0_ref[0], e1_ref[0]], axis=0)  # [2w, 1]
        vals = jnp.concatenate([v0_ref[0], v1_ref[0]], axis=0)
        run = jax.lax.broadcasted_iota(jnp.int32, (2 * w, BLOCK), 0)
        pos = p * BLOCK + jax.lax.broadcasted_iota(
            jnp.int32, (2 * w, BLOCK), 1)
        live = (run < w) | ~dup
        # the run holding each position: count of window ends <= pos
        # (runs before the window all ended by first_run's definition)
        covered = (ends <= pos) & live
        local = jnp.sum(covered.astype(jnp.int32), axis=0,
                        keepdims=True)                          # [1, BLOCK]
        onehot = (local == run) & live
        o_ref[0] = jnp.sum(jnp.where(onehot, vals, 0), axis=0,
                           keepdims=True)

    def window(shift):
        return pl.BlockSpec(
            (1, w, 1),
            lambda p, fr: (jnp.minimum(fr[p] // w + shift, nw - 1), 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # first_run
        grid=(nb,),
        in_specs=[window(0), window(1), window(0), window(1)],
        out_specs=pl.BlockSpec((1, 1, BLOCK), lambda p, fr: (p, 0, 0)),
    )

    def decode(first_run, values, ends):
        # the wire stays uint8/int32; the kernel computes in int32 (the
        # widening and the final narrowing are XLA's, on R- and N-sized
        # vectors beside a [2w, BLOCK] compare per output block)
        vcol = values.astype(jnp.int32).reshape(nw, w, 1)
        ecol = ends.astype(jnp.int32).reshape(nw, w, 1)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((nb, 1, BLOCK), jnp.int32),
            grid_spec=grid_spec,
            interpret=_interpret(),
        )(first_run, vcol, vcol, ecol, ecol)
        return out.reshape(n_pad).astype(jnp.uint8)

    return jax.jit(decode)


def decode_bytes(values: Any, ends: Any, first_run: np.ndarray,
                 n_pad: int, use_pallas: bool) -> Any:
    """values/ends already ON DEVICE -> decoded uint8[n_pad] on device.
    `first_run` stays a host array: it is the scalar-prefetch operand."""
    r = int(values.shape[0])
    if use_pallas:
        return _pallas_decode(r, int(n_pad))(first_run, values, ends)
    return _xla_decode(r, int(n_pad))(values, ends)
