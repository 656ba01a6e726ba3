"""Vision Transformer backbones for the model zoo.

Beyond-reference model family (the reference's CNTK zoo stops at CNNs —
SURVEY §2.9.6, downloader/ModelDownloader.scala:26-263): ViT is the
MXU-native image backbone.  ResNet-50's early stages are 1x1 convs over
large activations, bound by bandwidth; a ViT is almost entirely large
dense matmuls — patch embedding is a single [P²C, E] matmul, and every
block is LN + QKV/proj/MLP matmuls at S=196 — so its roofline sits where
the chip's FLOPs are, not its HBM.

TPU-first choices: NHWC uint8/f32 in, one conv-as-matmul patchify, bf16
compute with f32 params (flax default), static [B, 196, E] shapes, GAP
pooling by default (no CLS token: S stays 196 = 14², no ragged +1 that
costs a padded attention lane).  Encoder blocks are the SAME `_Block` as
TransformerLM (models/transformer.py) with non-causal attention — one
validated block implementation serves both model families.

Taps follow the zoo contract (ImageFeaturizer.scala:40-197 node
addressing): ["logits", "pool", "encoded", "embed"], `taps[layer_names[1]]`
is the penultimate feature vector.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from .transformer import _Block, default_attn

__all__ = ["VisionTransformer", "vit_tiny", "vit_small", "vit_base"]


class VisionTransformer(nn.Module):
    """ViT over NHWC images; GAP pooling, pre-LN encoder blocks."""

    patch_size: int = 16
    embed_dim: int = 192
    num_layers: int = 12
    num_heads: int = 3
    mlp_ratio: int = 4
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    # int8 inference: encoder matmuls run as int8 on the MXU (~2x the bf16
    # rate on v5e) via ops/quant.QuantDense — identical param pytree, so
    # quant=True scores weights trained with quant=False
    quant: bool = False
    # > 0: encoder MLPs become switch-MoE (V-MoE style); expert weights
    # shard over a mesh axis for expert parallelism
    moe_experts: int = 0
    # None -> transformer.default_attn(causal=False); or any (q, k, v) ->
    # out attention fn, the same contract as TransformerLM.attn_fn
    attn_fn: Optional[Callable] = None
    layer_names = ["logits", "pool", "encoded", "embed"]

    @nn.compact
    def __call__(self, x, train: bool = False) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        p = self.patch_size
        if x.shape[1] % p or x.shape[2] % p:
            raise ValueError(
                f"ViT needs input H/W divisible by patch_size={p}; got "
                f"{x.shape[1]}x{x.shape[2]} — resize (ImageFeaturizer does"
                " this automatically from bundle.input_shape)")
        taps: Dict[str, jnp.ndarray] = {}
        x = x.astype(self.dtype)
        # patchify as a conv: XLA lowers a stride-P PxP conv to one
        # [B*S, P*P*C] @ [P*P*C, E] matmul — pure MXU work
        x = nn.Conv(self.embed_dim, (p, p), strides=(p, p), padding="VALID",
                    dtype=self.dtype, name="patch_embed")(x)
        b, gh, gw, e = x.shape
        x = x.reshape(b, gh * gw, e)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, gh * gw, e), jnp.float32)
        x = x + pos.astype(self.dtype)
        taps["embed"] = x
        # shared dispatch rule with TransformerLM (transformer.default_attn):
        # flash kernel pair on a single TPU — S=196 pads to the 256 grid
        # with kv_valid masking — XLA dense under GSPMD sharding
        attn = (self.attn_fn if self.attn_fn is not None
                else default_attn(False))
        from ..ops.quant import dense_cls
        for i in range(self.num_layers):
            x = _Block(self.num_heads, self.mlp_ratio, self.dtype, attn,
                       dense_cls=dense_cls(self.quant),
                       num_experts=self.moe_experts, name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        taps["encoded"] = x
        pooled = jnp.mean(x, axis=1)
        taps["pool"] = pooled.astype(jnp.float32)
        logits = nn.Dense(self.num_classes, dtype=self.dtype,
                          name="head")(pooled).astype(jnp.float32)
        taps["logits"] = logits
        return logits, taps


def vit_tiny(num_classes=1000, dtype=jnp.bfloat16, patch_size=16,
             quant=False):
    return VisionTransformer(patch_size=patch_size, embed_dim=192,
                             num_layers=12, num_heads=3,
                             num_classes=num_classes, dtype=dtype,
                             quant=quant)


def vit_small(num_classes=1000, dtype=jnp.bfloat16, patch_size=16,
              quant=False):
    return VisionTransformer(patch_size=patch_size, embed_dim=384,
                             num_layers=12, num_heads=6,
                             num_classes=num_classes, dtype=dtype,
                             quant=quant)


def vit_base(num_classes=1000, dtype=jnp.bfloat16, patch_size=16,
             quant=False):
    return VisionTransformer(patch_size=patch_size, embed_dim=768,
                             num_layers=12, num_heads=12,
                             num_classes=num_classes, dtype=dtype,
                             quant=quant)
