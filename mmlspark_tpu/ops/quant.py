"""Int8 post-training-quantized inference ops.

Beyond-reference, TPU-first: a v5e's MXU runs int8 matmuls at ~2x its bf16
FLOP rate (394 TOPS vs 197 TFLOP/s), so inference-heavy paths (the
reference's CNTKModel scoring role, CNTKModel.scala:88-140) can trade a
little precision for double math throughput with NO retraining and NO
separate checkpoint format:

- `QuantDense` keeps the exact param pytree of `nn.Dense` (f32 kernel/bias)
  — any trained checkpoint loads unchanged; quantization happens inside the
  forward, on device.
- Weights: symmetric per-output-channel int8 (max|w|/127 scales).
- Activations: dynamic symmetric per-tensor int8, computed per call.
- The matmul itself runs int8 x int8 -> int32 on the MXU
  (`preferred_element_type=int32`), then dequantizes with one fused
  elementwise scale.

Numerics: symmetric scaling bounds |q| <= 127 by construction, so the int8
casts cannot overflow; int32 accumulation is exact for any k <= ~2^16
(127*127*k < 2^31), far past any layer width here.

Weights re-quantize inside each forward by default (they are jit
arguments, so XLA cannot fold them): the extra cost is one f32 kernel
read + elementwise round/cast per call — for ViT-B at batch 128 that is
~344MB against a ~23ms step, ~2% overhead, which keeping the checkpoint
format unchanged buys.  Where that traffic dominates — batch-1
autoregressive decode is weight-bandwidth-bound — `prequantize()` runs
one forward with the 'quant' collection mutable and stores each layer's
(int8 kernel, scales) beside the f32 params; subsequent applies read
int8 weights only (4x less HBM than f32, 2x less than bf16).  Run it
AFTER loading final weights: the cached int8 copy does not track later
param edits.
"""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = ["int8_dense", "int8_matmul", "quantize_weight", "QuantDense",
           "prequantize"]

_EPS = 1e-8


def quantize_weight(kernel: jnp.ndarray):
    """f32 `[K, N]` -> (int8 `[K, N]`, per-out-channel f32 scales `[N]`)."""
    kernel = kernel.astype(jnp.float32)
    ws = jnp.maximum(jnp.max(jnp.abs(kernel), axis=0), _EPS) / 127.0
    wq = jnp.round(kernel / ws).astype(jnp.int8)
    return wq, ws


def int8_matmul(x: jnp.ndarray, wq: jnp.ndarray, ws: jnp.ndarray,
                bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """`x [..., K]` against a prequantized (wq, ws) weight; returns f32.
    Activation scale is per-tensor dynamic (one max-reduce — cheap next
    to the matmul)."""
    x = x.astype(jnp.float32)
    xs = jnp.maximum(jnp.max(jnp.abs(x)), _EPS) / 127.0  # scalar, dynamic
    xq = jnp.round(x / xs).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * (xs * ws)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y


def int8_dense(x: jnp.ndarray, kernel: jnp.ndarray,
               bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """f32/bf16 `x [..., K] @ kernel [K, N]` executed as int8 on the MXU,
    quantizing the weight on the fly."""
    wq, ws = quantize_weight(kernel)
    return int8_matmul(x, wq, ws, bias)


class QuantDense(nn.Module):
    """Drop-in for `nn.Dense` with int8 compute.

    Same constructor surface and the same parameter names/shapes/dtypes
    (f32 'kernel' [K, N], optional 'bias' [N]) — swapping module classes
    re-uses trained weights as-is.  `dtype` is the OUTPUT dtype (matching
    nn.Dense's compute-dtype contract closely enough for the pre-LN
    transformer blocks here, whose next op casts anyway)."""

    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros,
                           (self.features,), jnp.float32)
                if self.use_bias else None)
        if self.has_variable("quant", "wq"):
            # prequantized weights (prequantize()): int8 reads only
            y = int8_matmul(x, self.get_variable("quant", "wq"),
                            self.get_variable("quant", "ws"), bias)
        elif not self.is_initializing() and self.is_mutable_collection("quant"):
            # the prequant pass itself: compute once, store beside params.
            # (never during init — a 'quant' snapshot of random init
            # weights would go stale the moment trained params land)
            wq, ws = quantize_weight(kernel)
            self.put_variable("quant", "wq", wq)
            self.put_variable("quant", "ws", ws)
            y = int8_matmul(x, wq, ws, bias)
        else:
            y = int8_dense(x, kernel, bias)
        return y.astype(self.dtype)


def rounded_to(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """float32 x with the values it would have at `dtype`, still float32,
    by an operation the compiler keeps (`reduce_precision`).  Of a bare
    `astype` pair (f32 -> bf16 -> f32) it may drop the rounding for one
    consumer and keep it for another (excess precision is allowed on the
    TPU): a router and a tap of its input then read different numbers,
    and a hi + lo split of a query loses its lo."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def quantize_kv_row(x: jnp.ndarray):
    """[..., H, D] K/V rows -> (int8 rows, f32 per-row-per-head scales
    [..., H]).  Symmetric per-(position, head) scaling: each attention
    row dequantizes exactly like int8_matmul's weights do."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                    _EPS) / 127.0
    q = jnp.round(x.astype(jnp.float32) / s[..., None]).astype(jnp.int8)
    return q, s


def dense_cls(quant: bool):
    """The one quant -> dense-class selection both model families use."""
    return QuantDense if quant else nn.Dense


def prequantize(model: nn.Module, variables: dict, sample_input,
                **apply_kwargs) -> dict:
    """One forward with the 'quant' collection mutable: every QuantDense
    stores its (int8 kernel, scales), and the returned variables dict
    carries them beside the unchanged f32 params.  Call AFTER final
    weights are loaded; re-call after any param update."""
    # strip any existing quant collection: QuantDense prefers stored int8
    # weights, so leaving it in would re-emit the stale copy verbatim
    fresh = {c: v for c, v in variables.items() if c != "quant"}
    _, mutated = model.apply(fresh, sample_input,
                             mutable=["quant"], **apply_kwargs)
    if "quant" not in mutated:
        raise ValueError(
            "prequantize: the model has no QuantDense layers — build it "
            "with quant=True (vit_*/transformer_lm) or use QuantDense "
            "directly")
    return {**fresh, "quant": mutated["quant"]}
