"""The one way the benchmark builds the language model of a configuration
file: `transformer_lm` (what a user calls) at the file's widths."""
from __future__ import annotations

from lib.flops import lm_sizes


def build_lm(config: dict, max_len: int):
    """(sizes, TransformerLM) in bf16 with `max_len` positions."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.transformer import transformer_lm

    s = lm_sizes(config)
    return s, transformer_lm(vocab_size=s["vocab"], embed_dim=s["e"],
                             num_layers=s["layers"], num_heads=s["heads"],
                             max_len=max_len, dtype=jnp.bfloat16)
