"""Image pipeline stages: the opencv-module + core/image equivalents.

Reference:
  - ImageTransformer (opencv/.../ImageTransformer.scala:282-400): a list of
    named ops (resize/crop/colorFormat/flip/blur/threshold/gaussianKernel)
    compiled per partition and applied per row via OpenCV Mats.
  - ResizeImageTransformer (core/image/ResizeImageTransformer.scala)
  - UnrollImage / UnrollBinaryImage (core/image/UnrollImage.scala:30-232)
  - ImageSetAugmenter (opencv/.../ImageSetAugmenter.scala)

TPU-first design: instead of per-row Mat calls, the op list is traced once
into a single jitted function over a `[B,H,W,C] float32` batch; rows are
grouped by shape so XLA sees static shapes, and the whole pipeline fuses into
one program per shape group.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.params import Param, TypeConverters
from ..core.pipeline import Transformer
from ..core.registry import register_stage
from ..core.schema import Table
from ..io.image import array_to_image_row, image_row_to_array, safe_read
from . import image as I

__all__ = [
    "ImageTransformer",
    "ResizeImageTransformer",
    "UnrollImage",
    "UnrollBinaryImage",
    "ImageSetAugmenter",
]


def _rows_to_shape_groups(col: np.ndarray) -> Dict[Tuple[int, int, int], List[int]]:
    groups: Dict[Tuple[int, int, int], List[int]] = {}
    for i, row in enumerate(col):
        arr_shape = (row["height"], row["width"], row["nChannels"])
        groups.setdefault(arr_shape, []).append(i)
    return groups


def _decode_cell(v: Any) -> Optional[Dict[str, Any]]:
    """Accept image rows, raw encoded bytes, or ndarray."""
    if v is None:
        return None
    if isinstance(v, dict):
        return v
    if isinstance(v, (bytes, bytearray)):
        return safe_read(bytes(v))
    if isinstance(v, np.ndarray) and v.ndim >= 2:
        return array_to_image_row(v)
    return None


def decode_cells(col: np.ndarray) -> list:
    """Decode a whole image column (rows/bytes/arrays -> image rows, None
    for undecodable cells).  PIL's and the native decoder's codecs release
    the GIL, so larger columns decode thread-parallel — the shared host
    decode policy of ImageFeaturizer/DeepVisionClassifier (the reference
    decodes per-row on JVM task threads, ImageUtils.scala:26).

    Cells that are already decoded (image-row dicts, ndarray pixels) are
    short-circuited inline BEFORE the pool: only encoded bytes pay a
    codec, and a column of mostly-decoded rows with a few encoded
    stragglers no longer spins up 16 threads to re-wrap ndarrays.  Wall
    time and item count land in the pipeline telemetry's "decode" stage,
    so a per-stage breakdown covers this path too."""
    import os
    import time

    from ..core import telemetry as core_telemetry
    from ..io.pipeline import PIPELINE_TELEMETRY

    out: list = [None] * len(col)
    pending: list = []  # indices still needing a codec (bytes/unknown)
    for i, v in enumerate(col):
        if v is None:
            continue
        if isinstance(v, dict):
            out[i] = v
        elif isinstance(v, np.ndarray) and v.ndim >= 2:
            out[i] = array_to_image_row(v)
        else:
            pending.append(i)
    if not pending:
        return out
    t0 = time.perf_counter()
    if len(pending) > 32:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(
                max_workers=min(16, os.cpu_count() or 4)) as ex:
            rows = list(ex.map(_decode_cell, (col[i] for i in pending)))
    else:
        rows = [_decode_cell(col[i]) for i in pending]
    for i, row in zip(pending, rows):
        out[i] = row
    dt = time.perf_counter() - t0
    PIPELINE_TELEMETRY.add("decode", busy_s=dt, items=len(pending))
    core_telemetry.histogram("io.pipeline.stage.latency",
                             stage="decode").observe(dt)
    return out


class _BatchedImageStage(Transformer):
    """Shared machinery: gather image rows -> same-shape float32 batches ->
    jitted op pipeline -> scatter back."""

    input_col = Param("image column", default="image")
    output_col = Param("output column", default=None)

    def _pipeline_fn(self) -> Callable[[jnp.ndarray], jnp.ndarray]:
        raise NotImplementedError

    def _float_output(self) -> bool:
        """When True, emit float arrays instead of uint8 image rows (e.g. a
        pipeline ending in normalize would be destroyed by uint8 clipping)."""
        return False

    def _emit(self, out_batch: np.ndarray, src_rows: List[dict]) -> List[Any]:
        if self._float_output():
            return [np.asarray(a, dtype=np.float32) for a in out_batch]
        return [
            array_to_image_row(np.clip(a, 0, 255).astype(np.uint8),
                               origin=r.get("origin", ""))
            for a, r in zip(out_batch, src_rows)
        ]

    def _run_group(self, batch: np.ndarray) -> np.ndarray:
        """One same-shape float32 batch -> output batch.  Base: the jitted
        op-list composition, cached per stage instance AND current param
        values — a param mutation after a transform invalidates the cache
        (jit re-specializes per input shape as usual)."""
        token = repr(sorted(self.simple_param_values().items()))
        cached = self.__dict__.get("_jitted_pipeline")
        if cached is None or cached[0] != token:
            from ..core import telemetry as core_telemetry
            cached = (token, core_telemetry.watch_compiles(
                jax.jit(self._pipeline_fn()),
                name=f"image_stages.{type(self).__name__}"))
            self.__dict__["_jitted_pipeline"] = cached
        return np.asarray(cached[1](jnp.asarray(batch)))

    def _transform(self, table: Table) -> Table:
        out_col = self.output_col or self.input_col
        cells = [_decode_cell(v) for v in table[self.input_col]]
        result: List[Any] = [None] * table.num_rows
        valid_idx = [i for i, c in enumerate(cells) if c is not None]
        valid = np.empty(len(valid_idx), dtype=object)
        for j, i in enumerate(valid_idx):
            valid[j] = cells[i]
        for _shape, members in _rows_to_shape_groups(valid).items():
            rows = [valid[m] for m in members]
            batch = np.stack([image_row_to_array(r) for r in rows]).astype(np.float32)
            out = self._run_group(batch)
            for r_out, m in zip(self._emit(out, rows), members):
                result[valid_idx[m]] = r_out
        return table.with_column(out_col, result)


@register_stage
class ImageTransformer(_BatchedImageStage):
    """Op-list image preprocessing — the OpenCV ImageTransformer equivalent
    (ImageTransformer.scala:282-400).  Ops are (name, kwargs) pairs added
    fluently; the list compiles to ONE fused XLA program.
    """

    stages = Param("list of [op_name, kwargs] pairs", default=None)
    fuse = Param(
        "fold the whole op list into ONE two-matmul Pallas pass when every "
        "op is separable-linear (crop/resize/flip/blur/color/normalize): "
        "None = auto (real TPU only), False = always the XLA composition",
        default=None)

    _OPS = {
        "resize": lambda b, height, width, method="linear": I.resize(b, height, width, method),
        "crop": lambda b, x, y, width, height: I.crop(b, x, y, width, height),
        "centerCrop": lambda b, height, width: I.center_crop(b, height, width),
        "colorFormat": lambda b, format: I.color_convert(b, format),
        "flip": lambda b, flipLeftRight=True, flipUpDown=False: I.flip(b, flipLeftRight, flipUpDown),
        "blur": lambda b, height, width: I.box_blur(b, int(height), int(width)),
        "gaussianKernel": lambda b, apertureSize, sigma: I.gaussian_blur(b, int(apertureSize), sigma),
        "threshold": lambda b, threshold, maxVal, thresholdType="binary": I.threshold(
            b, threshold, maxVal, thresholdType),
        "normalize": lambda b, mean, std, scale=1.0: I.normalize(b, mean, std, scale),
    }

    # ---- fluent builders (mirroring the reference's setter API) -------
    def _add(self, name: str, **kwargs) -> "ImageTransformer":
        ops = list(self.stages or [])
        ops.append([name, kwargs])
        self.set(stages=ops)
        return self

    def resize(self, height: int, width: int, method: str = "linear"):
        return self._add("resize", height=height, width=width, method=method)

    def crop(self, x: int, y: int, width: int, height: int):
        return self._add("crop", x=x, y=y, width=width, height=height)

    def center_crop(self, height: int, width: int):
        return self._add("centerCrop", height=height, width=width)

    def color_format(self, format: str):
        return self._add("colorFormat", format=format)

    def flip(self, flip_left_right: bool = True, flip_up_down: bool = False):
        return self._add("flip", flipLeftRight=flip_left_right, flipUpDown=flip_up_down)

    def blur(self, height: float, width: float):
        return self._add("blur", height=height, width=width)

    def gaussian_kernel(self, aperture_size: int, sigma: float):
        return self._add("gaussianKernel", apertureSize=aperture_size, sigma=sigma)

    def threshold(self, threshold: float, max_val: float, threshold_type: str = "binary"):
        return self._add("threshold", threshold=threshold, maxVal=max_val,
                         thresholdType=threshold_type)

    def normalize(self, mean, std, scale: float = 1.0):
        return self._add("normalize", mean=mean, std=std, scale=scale)

    def _float_output(self) -> bool:
        # a normalize (or sub-1 threshold) tail produces float-scale values;
        # clipping those to uint8 would zero them out
        for name, kwargs in self.stages or []:
            if name == "normalize":
                return True
            if name == "threshold" and kwargs.get("maxVal", 255) <= 1.0:
                return True
        return False

    def _pipeline_fn(self):
        ops = [(self._OPS[name], dict(kwargs)) for name, kwargs in (self.stages or [])]

        def run(batch):
            for fn, kwargs in ops:
                batch = fn(batch, **kwargs)
            return batch

        return run

    def _fuse_wanted(self) -> bool:
        f = self.get_or_default("fuse")
        if f is None:  # auto: interpret-mode Pallas on CPU is slower than XLA
            from .pallas_kernels import on_tpu

            return on_tpu()
        return bool(f)

    def _run_group(self, batch: np.ndarray) -> np.ndarray:
        if self._fuse_wanted():
            from .pallas_kernels import (
                affine_plan, freeze_stages, fused_affine_apply)

            plan = affine_plan(freeze_stages(self.stages),
                               *batch.shape[1:],
                               itemsize=batch.dtype.itemsize)
            if plan is not None:
                return np.asarray(fused_affine_apply(jnp.asarray(batch),
                                                     plan))
        return super()._run_group(batch)


@register_stage
class ResizeImageTransformer(_BatchedImageStage):
    """Resize-only stage (core/image/ResizeImageTransformer.scala)."""

    height = Param("target height", converter=TypeConverters.to_int)
    width = Param("target width", converter=TypeConverters.to_int)
    method = Param("linear|nearest|cubic", default="linear")

    def _pipeline_fn(self):
        h, w, m = self.height, self.width, self.method
        return lambda b: I.resize(b, h, w, m)


@register_stage
class UnrollImage(_BatchedImageStage):
    """Image rows -> flat CHW float vector column
    (core/image/UnrollImage.scala:30-55: unsigned-byte fix + c*h*w layout).

    The unroll (+ optional per-channel normalize) runs as the fused Pallas
    kernel (ops/pallas_kernels.py) — one HBM round-trip per image."""

    input_col = Param("image column", default="image")
    output_col = Param("vector column", default="unrolled")
    mean = Param("per-channel mean to subtract", default=None,
                 converter=TypeConverters.to_list_float)
    std = Param("per-channel std to divide", default=None,
                converter=TypeConverters.to_list_float)

    def _pipeline_fn(self):
        from .pallas_kernels import fused_normalize_unroll

        mean = self.get_or_default("mean") or (0.0,)
        std = self.get_or_default("std") or (1.0,)
        return lambda batch: fused_normalize_unroll(batch, mean, std)

    def _emit(self, out_batch, src_rows):
        return [np.asarray(v, dtype=np.float64) for v in out_batch]


@register_stage
class UnrollBinaryImage(_BatchedImageStage):
    """Raw encoded bytes -> (optional resize) -> flat CHW vector
    (UnrollImage.scala:161-232, UnrollBinaryImage)."""

    input_col = Param("binary column", default="bytes")
    output_col = Param("vector column", default="unrolled")
    height = Param("optional resize height", default=None)
    width = Param("optional resize width", default=None)

    def _pipeline_fn(self):
        h, w = self.height, self.width

        def run(batch):
            if h is not None and w is not None:
                batch = I.resize(batch, int(h), int(w))
            return I.hwc_to_chw_flat(batch)

        return run

    def _emit(self, out_batch, src_rows):
        return [np.asarray(v, dtype=np.float64) for v in out_batch]


@register_stage
class ImageSetAugmenter(Transformer):
    """Train-time augmentation: emit original + flipped copies
    (opencv/.../ImageSetAugmenter.scala:77)."""

    input_col = Param("image column", default="image")
    output_col = Param("output column", default="image")
    flip_left_right = Param("emit LR-flipped copy", default=True,
                            converter=TypeConverters.to_bool)
    flip_up_down = Param("emit UD-flipped copy", default=False,
                         converter=TypeConverters.to_bool)

    def _transform(self, table: Table) -> Table:
        parts = [table.with_column(self.output_col, table[self.input_col])]
        flips = []
        if self.flip_left_right:
            flips.append((True, False))
        if self.flip_up_down:
            flips.append((False, True))
        for lr, ud in flips:
            t = ImageTransformer(input_col=self.input_col, output_col=self.output_col)
            t.flip(flip_left_right=lr, flip_up_down=ud)
            parts.append(t.transform(table))
        return Table.concat(parts)
