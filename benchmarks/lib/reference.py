"""The plain references the cells are compared with: straightforward
`jax.numpy` in float32 under `highest` matmul precision, no kernels, no
cache, no batching tricks, written from the published description and
reading the program's parameter tree only for its numbers.

GPT-2 (Radford et al. 2019; openai-community `config.json`): learned
positions, pre-LayerNorm blocks, GELU (tanh approximation), causal
softmax attention.  Departures of the served model, stated in the
configuration files under `assumed` and reproduced here because they are
what the weights mean: qkv and proj without bias, LayerNorm epsilon 1e-6,
an output head untied from the embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def lm_logits(params, tokens, num_layers: int, num_heads: int):
    """[B, S] int32 -> [B, S, V] float32 logits."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        b, s = tokens.shape
        x = (params["tok_embed"]["embedding"][tokens]
             + params["pos_embed"]["embedding"][:s][None])
        e = x.shape[-1]
        d = e // num_heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(num_layers):
            p = params[f"block{i}"]
            y = _layer_norm(x, p["ln1"])
            qkv = (y @ p["qkv"]["kernel"]).reshape(b, s, 3 * num_heads, d)
            q, k, v = jnp.split(qkv, 3, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(d))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
            x = x + a.reshape(b, s, e) @ p["proj"]["kernel"]
            y = _layer_norm(x, p["ln2"])
            y = jax.nn.gelu(y @ p["mlp_in"]["kernel"] + p["mlp_in"]["bias"],
                            approximate=True)
            x = x + y @ p["mlp_out"]["kernel"] + p["mlp_out"]["bias"]
        x = _layer_norm(x, params["ln_f"])
        return x @ params["head"]["kernel"]


def lm_loss(params, tokens, num_layers: int, num_heads: int):
    """Mean next-token cross-entropy of [B, S] tokens, float32."""
    logits = lm_logits(params, tokens, num_layers, num_heads)[:, :-1]
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - picked)
