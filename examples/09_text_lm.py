"""Text end-to-end: BPE tokenizer -> TransformerLM -> text completions.

The tokenizer is a pipeline stage (fit on a text column, emits int32 id
arrays); the LM trains on its output with the scanned-epoch factory; and
decoding goes ids -> text through the same fitted vocabulary — the whole
LM lifecycle with no hand-rolled token bookkeeping.

Run: python examples/09_text_lm.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp
import optax

from mmlspark_tpu import Table
from mmlspark_tpu.featurize.tokenizer import BPETokenizer, pack_sequences
from mmlspark_tpu.models.generation import generate
from mmlspark_tpu.models.training import make_lm_train_epoch
from mmlspark_tpu.models.transformer import transformer_lm

FAST = os.environ.get("MMLSPARK_EXAMPLE_FAST") not in (None, "", "0")

# ---- a tiny corpus with a learnable continuation pattern ----------------
SENTENCES = [
    "the cat sat on the mat",
    "the dog sat on the log",
    "the bird sat on the wire",
    "the frog sat on the stone",
]
corpus = Table({"text": SENTENCES * 4})

# ---- tokenize (a fitted stage, like any other featurizer) ---------------
tok = BPETokenizer(vocab_size=96, append_eos=True).fit(corpus)
rows = tok.transform(corpus)["tokens"]
print(f"vocab={len(tok.vocab)} tokens; "
      f"'{SENTENCES[0]}' -> {rows[0].tolist()}")

SEQ = max(len(r) for r in rows)
padded = pack_sequences(rows, SEQ)  # mode='pack' would GPT-chunk instead

# ---- train the LM on token ids ------------------------------------------
model = transformer_lm(vocab_size=len(tok.vocab), embed_dim=48,
                       num_layers=2, num_heads=4, max_len=2 * SEQ,
                       dtype=jnp.float32)
toks = jnp.asarray(padded.reshape(2, 8, SEQ))
params = model.init({"params": jax.random.PRNGKey(0)}, toks[0],
                    train=False)["params"]
opt = optax.adam(8e-3)
opt_state = opt.init(params)
epoch = make_lm_train_epoch(model, opt, donate=False)
for _ in range(60 if FAST else 120):
    params, opt_state, losses = epoch(params, opt_state, toks)
print(f"final next-token loss: {float(losses[-1]):.4f}")

# ---- complete text prompts ----------------------------------------------
variables = {"params": params}
for prompt_text in ("the cat sat", "the bird sat"):
    ids = tok.encode(prompt_text, append_eos=False)[None]
    out = generate(model, variables, jnp.asarray(ids),
                   max_new_tokens=8, eos_id=tok.eos_id)
    completion = tok.decode(np.asarray(out)[0])
    print(f"{prompt_text!r} -> {completion!r}")
    want = {"the cat sat": "the cat sat on the mat",
            "the bird sat": "the bird sat on the wire"}[prompt_text]
    assert completion == want, (completion, want)
print("text completions match the learned corpus")
