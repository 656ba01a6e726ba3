"""Serve a language model over HTTP: train, then generate per request.

A TransformerLM learns a token stream, and a serving endpoint completes
prompts with the KV-cached decode loop — prompts of mixed lengths in one
continuous batch are grouped by length so every generate call keeps
static shapes (the featurizer's shape-group pattern).  Beyond-reference:
the reference serves fixed-function models only.

Run: python examples/07_serve_language_model.py
"""
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp
import optax

from mmlspark_tpu import Table
from mmlspark_tpu.core.pipeline import LambdaTransformer
from mmlspark_tpu.models.generation import generate
from mmlspark_tpu.models.training import make_lm_train_epoch
from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.serving import read_stream

VOCAB, SEQ = 64, 32
FAST = os.environ.get("MMLSPARK_EXAMPLE_FAST") not in (None, "", "0")

# ---- train on a modular counting stream (one scanned epoch per loop) ----
model = transformer_lm(vocab_size=VOCAB, embed_dim=32, num_layers=2,
                       num_heads=4, max_len=SEQ, dtype=jnp.float32)
steps, batch = 8, 8
base = (np.arange(steps * batch).reshape(steps, batch, 1)
        + np.arange(SEQ)[None, None, :]) % VOCAB
tokens = jnp.asarray(base, jnp.int32)
params = model.init({"params": jax.random.PRNGKey(0)}, tokens[0],
                    train=False)["params"]
opt = optax.adam(3e-3)
opt_state = opt.init(params)
epoch = make_lm_train_epoch(model, opt, donate=False)
for e in range(12 if FAST else 20):
    params, opt_state, losses = epoch(params, opt_state, tokens)
print(f"final next-token loss: {float(losses[-1]):.4f}")

# ---- serve: prompt token ids in, completion out -------------------------
variables = {"params": params}


def complete(t: Table) -> Table:
    prompts = [np.asarray(p, np.int32) for p in t["prompt"]]
    groups = {}
    for i, p in enumerate(prompts):
        groups.setdefault(len(p), []).append(i)
    out = [None] * len(prompts)
    for _n, idxs in groups.items():
        gen = generate(model, variables,
                       jnp.asarray(np.stack([prompts[i] for i in idxs])),
                       max_new_tokens=8)
        for i, row in zip(idxs, np.asarray(gen)):
            out[i] = row.tolist()
    return t.with_column("completion", out)


query = (read_stream()
         .continuous_server(name="lm", path="/generate")
         .parse_request(schema=["prompt"])
         .transform(LambdaTransformer(fn=complete))
         .make_reply("completion")
         .options(batch_timeout_ms=5.0)
         .start())


def post(prompt):
    body = json.dumps({"prompt": prompt}).encode()
    req = urllib.request.Request(
        query.service_info.url, data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())["completion"]


try:
    # ragged prompt lengths (grouped per generate call); >=4 tokens so
    # even a briefly-trained model sees the pattern unambiguously
    for prompt in ([5, 6, 7, 8], [40, 41, 42, 43, 44, 45]):
        completion = post(prompt)
        print(f"prompt {prompt} -> completion {completion[len(prompt):]}")
        want = [(prompt[-1] + 1 + i) % VOCAB for i in range(8)]
        assert completion[len(prompt):] == want, (completion, want)
    print("served completions continue the learned sequence")
finally:
    query.stop()

# ---- and the same model as a token-streaming endpoint -------------------
# stream_reply flushes each chunk to the client as it is produced
# (Transfer-Encoding: chunked over the held exchange)


def stream_tokens(row):
    toks = jnp.asarray(np.asarray(row["prompt"], np.int32))[None]
    out = np.asarray(generate(model, variables, toks, max_new_tokens=8))
    for t in out[0, toks.shape[1]:]:
        yield f"{int(t)} "


squery = (read_stream()
          .continuous_server(name="lm-stream", path="/stream")
          .parse_request(schema=["prompt"])
          .stream_reply(stream_tokens)
          .options(batch_timeout_ms=5.0)
          .start())
try:
    import http.client

    info = squery.service_info
    conn = http.client.HTTPConnection(info.host, info.port, timeout=30)
    conn.request("POST", "/stream", body=json.dumps(
        {"prompt": [20, 21, 22, 23]}).encode(),
        headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    streamed = [int(t) for t in resp.read().decode().split()]
    conn.close()
    print(f"streamed completion: {streamed}")
    assert streamed == [(24 + i) % VOCAB for i in range(8)], streamed
    print("token-streaming endpoint serves the same weights")
finally:
    squery.stop()
