"""Int8 quantized inference: train in f32, serve in int8 — no conversion.

A small TransformerLM learns a token stream, then the SAME weights run
through the int8 path (ops/quant.py): `transformer_lm(quant=True)` swaps
every block/head matmul for QuantDense, and `prequantize` stores each
layer's (int8 kernel, scales) beside the f32 params so batch-1 KV-cached
decode — weight-bandwidth-bound — reads int8 weights only (~2x token rate
on a v5e vs bf16, 4x less HBM than f32).

Run: python examples/08_quantized_inference.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp
import optax

from mmlspark_tpu.models.generation import generate
from mmlspark_tpu.models.training import make_lm_train_epoch
from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.ops.quant import prequantize

VOCAB, SEQ = 64, 32
FAST = os.environ.get("MMLSPARK_EXAMPLE_FAST") not in (None, "", "0")

# ---- train in full precision (the normal path) --------------------------
cfg = dict(vocab_size=VOCAB, embed_dim=32, num_layers=2, num_heads=4,
           max_len=SEQ, dtype=jnp.float32)
model = transformer_lm(**cfg)
steps, batch = 8, 8
base = (np.arange(steps * batch).reshape(steps, batch, 1)
        + np.arange(SEQ)[None, None, :]) % VOCAB
tokens = jnp.asarray(base, jnp.int32)
params = model.init({"params": jax.random.PRNGKey(0)}, tokens[0],
                    train=False)["params"]
opt = optax.adam(3e-3)
opt_state = opt.init(params)
epoch = make_lm_train_epoch(model, opt, donate=False)
for _ in range(8 if FAST else 25):
    params, opt_state, losses = epoch(params, opt_state, tokens)
print(f"trained f32, final next-token loss {float(losses[-1]):.4f}")

# ---- quantize for serving: same weights, int8 compute -------------------
qmodel = transformer_lm(**cfg, quant=True)
qvars = prequantize(qmodel, {"params": params}, tokens[0, :1])
n_int8 = sum(v.size for v in jax.tree.leaves(qvars["quant"])
             if v.dtype == jnp.int8)
print(f"prequantized {n_int8} weights to int8 "
      "(f32 params untouched — one checkpoint serves both paths)")

# logits stay faithful...
lg_f32, _ = model.apply({"params": params}, tokens[0, :2])
lg_int8, _ = qmodel.apply(qvars, tokens[0, :2])
corr = np.corrcoef(np.asarray(lg_f32).ravel(),
                   np.asarray(lg_int8).ravel())[0, 1]
print(f"f32-vs-int8 logit correlation: {corr:.4f}")
assert corr > 0.99, corr

# ...and so do greedy completions of the learned sequence
prompt = jnp.asarray([[10, 11, 12, 13]], jnp.int32)
out_f32 = np.asarray(generate(model, {"params": params}, prompt,
                              max_new_tokens=8))[0, 4:]
out_int8 = np.asarray(generate(qmodel, qvars, prompt,
                               max_new_tokens=8))[0, 4:]
print(f"f32 decode:  {out_f32.tolist()}")
print(f"int8 decode: {out_int8.tolist()}")
agree = int((out_f32 == out_int8).sum())
assert agree >= 6, f"int8 decode diverged: {agree}/8 tokens agree"
print(f"int8 greedy decode matches f32 on {agree}/8 tokens")

# ---- self-speculation: the int8 model drafts for its f32 self ----------
# same weights, so acceptance is near-perfect; on a v5e the draft runs
# ~2x the f32 rate, and the OUTPUT is provably the f32 greedy decode
from mmlspark_tpu.models.generation import speculative_generate

spec, rounds = speculative_generate(
    model, {"params": params}, qmodel, qvars, prompt,
    max_new_tokens=8, gamma=4, return_stats=True)
assert np.array_equal(np.asarray(spec)[0, 4:], out_f32)
print(f"self-speculative decode: exact f32 output in {int(rounds)} target "
      f"forwards (vs 8 token-by-token)")
