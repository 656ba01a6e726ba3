"""Long-context TransformerLM: train a tiny LM, then score the SAME
parameters with exact ring attention over a sequence-sharded mesh.

The attention implementation is a constructor argument, so one set of
weights moves between single-chip dense attention and sequence-parallel
ring attention (parallel/ring_attention.py) with identical numerics —
the recipe for contexts larger than one chip's HBM.

CPU-safe: run with
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/06_long_context_transformer.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import optax

from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.parallel.mesh import MeshContext, make_mesh
from mmlspark_tpu.parallel.ring_attention import ring_attention

VOCAB, SEQ, BATCH = 64, 32, 8

rng = np.random.default_rng(0)
model = transformer_lm(vocab_size=VOCAB, embed_dim=32, num_layers=2,
                       num_heads=4, max_len=SEQ, dtype=jnp.float32)
variables = model.init({"params": jax.random.PRNGKey(0)},
                       jnp.zeros((1, SEQ), jnp.int32), train=False)
params = variables["params"]

# a learnable toy pattern: next token = (token + 1) mod VOCAB
base = rng.integers(0, VOCAB, (BATCH * 8, 1))
tokens = ((base + np.arange(SEQ)) % VOCAB).astype(np.int32)

opt = optax.adam(3e-3)
opt_state = opt.init(params)


@jax.jit
def step(params, opt_state, batch):
    def loss_fn(p):
        logits, _ = model.apply({"params": p}, batch, train=False)
        lp = jax.nn.log_softmax(logits[:, :-1])
        tgt = batch[:, 1:]
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], -1))

    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = opt.update(grads, opt_state)
    return optax.apply_updates(params, updates), opt_state, loss


for epoch in range(30):
    for start in range(0, len(tokens), BATCH):
        params, opt_state, loss = step(params, opt_state,
                                       tokens[start:start + BATCH])
print(f"final next-token loss: {float(loss):.4f}")

# score the SAME weights sequence-parallel: ring attention over 'seq'
mesh = make_mesh(data=1, seq=jax.device_count())
ringed = transformer_lm(
    vocab_size=VOCAB, embed_dim=32, num_layers=2, num_heads=4, max_len=SEQ,
    dtype=jnp.float32,
    attn_fn=partial(ring_attention, mesh=mesh, causal=True))
probe = tokens[:2]
with MeshContext(mesh):
    sp_logits, _ = ringed.apply({"params": params}, jnp.asarray(probe))
dense_logits, _ = model.apply({"params": params}, jnp.asarray(probe))
diff = float(jnp.abs(sp_logits - dense_logits).max())
print(f"seq-parallel vs dense max diff: {diff:.2e} "
      f"(sp={jax.device_count()} devices)")
pred = np.asarray(jnp.argmax(sp_logits[:, :-1], -1))
acc = float((pred == probe[:, 1:]).mean())
print(f"next-token accuracy (ring attention): {acc:.2f}")
assert diff < 1e-3 and acc > 0.9

# generate from the trained weights: ONE prefill forward + ONE scanned
# KV-cached decode loop (no per-token host round trips)
from mmlspark_tpu.models.generation import generate

prompt = jnp.asarray(tokens[:1, :8])
out = generate(model, {"params": params}, prompt, max_new_tokens=16)
print("prompt   :", np.asarray(prompt)[0].tolist())
print("generated:", np.asarray(out)[0, 8:].tolist())
# the data is modular counting: the cached decode must continue it
cont = np.asarray(out)[0, 8:]
want = [(int(prompt[0, -1]) + 1 + i) % VOCAB for i in range(16)]
assert out.shape == (1, 24) and cont.tolist() == want
print("continuation correct: the KV-cached decode tracks the sequence")
