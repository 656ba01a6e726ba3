"""The whole LM service in one call: tokenizer + continuous batching +
token streaming behind `read_stream().generate_stream(...)`.

Builds on examples 07/09: a BPE tokenizer fits the corpus, a
TransformerLM learns it, and ONE fluent chain serves text completions —
concurrent clients share a slotted device batch, chunks stream as
decoded, and stopping the query stops the decode loop.

Run: python examples/10_lm_service_one_call.py
"""
import http.client
import json
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from mmlspark_tpu import Table
from mmlspark_tpu.featurize.tokenizer import BPETokenizer, pack_sequences
from mmlspark_tpu.models.training import make_lm_train_epoch
from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.serving import read_stream

FAST = os.environ.get("MMLSPARK_EXAMPLE_FAST") not in (None, "", "0")

SENTENCES = [
    "the cat sat on the mat",
    "the dog sat on the log",
    "the bird sat on the wire",
    "the frog sat on the stone",
]
corpus = Table({"text": SENTENCES * 4})

tok = BPETokenizer(vocab_size=96, append_eos=True).fit(corpus)
rows = tok.transform(corpus)["tokens"]
SEQ = max(len(r) for r in rows)
toks = jnp.asarray(pack_sequences(rows, SEQ).reshape(2, 8, SEQ))

model = transformer_lm(vocab_size=len(tok.vocab), embed_dim=48,
                       num_layers=2, num_heads=4, max_len=2 * SEQ,
                       dtype=jnp.float32)
params = model.init({"params": jax.random.PRNGKey(0)}, toks[0],
                    train=False)["params"]
opt = optax.adam(8e-3)
opt_state = opt.init(params)
epoch = make_lm_train_epoch(model, opt, donate=False)
for _ in range(60 if FAST else 120):
    params, opt_state, losses = epoch(params, opt_state, toks)
print(f"trained: final loss {float(losses[-1]):.4f}")

# ---- serve: one call wires tokenizer + batcher + streaming --------------
query = (read_stream()
         .continuous_server(name="lm-svc", path="/complete")
         .parse_request(schema=["prompt"])
         .generate_stream(model, {"params": params}, tokenizer=tok,
                          max_new_tokens=8, max_slots=4)
         .options(batch_timeout_ms=5.0)
         .start())

WANT = {"the cat sat": "on the mat",
        "the bird sat": "on the wire",
        "the frog sat": "on the stone"}
results = {}


def client(prompt):
    conn = http.client.HTTPConnection(query.service_info.host,
                                      query.service_info.port, timeout=30)
    conn.request("POST", "/complete",
                 body=json.dumps({"prompt": prompt}).encode())
    results[prompt] = conn.getresponse().read().decode().strip()
    conn.close()


try:
    threads = [threading.Thread(target=client, args=(p,), daemon=True,
                                name=f"example-lm-client-{i}")
               for i, p in enumerate(WANT)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
finally:
    query.stop()

for prompt, want in WANT.items():
    got = results[prompt]
    print(f"{prompt!r} -> {got!r}")
    assert got == want, (prompt, got, want)
print("three concurrent clients streamed exact completions off one "
      "slotted device batch")
