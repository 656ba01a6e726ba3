"""The modern LM serving stack in one script: shared system prompt +
paged KV + speculative decoding, all exactness-preserving.

A "system prompt" prefills ONCE into read-only shared pages
(register_prefix); every completion request reuses those pages and
prefills only its own suffix.  The KV cache is paged (pay-per-page HBM
with reservation-based admission control), and a small draft model
proposes token blocks that one target forward verifies per tick
(speculative continuous batching).  Every stream still emits EXACTLY
the target model's greedy generate() tokens — the machinery only
changes how much compute and memory each token costs.

Run: python examples/13_system_prompt_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from mmlspark_tpu.models.generation import generate
from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.serving.batcher import ContinuousBatcher


def tiny_lm(seed, embed=48, layers=2, heads=2):
    model = transformer_lm(vocab_size=96, embed_dim=embed,
                           num_layers=layers, num_heads=heads,
                           max_len=96, dtype=jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(seed)},
                           jnp.zeros((1, 4), jnp.int32), train=False)
    return model, {c: v for c, v in variables.items() if c != "kvcache"}


def main():
    target, tv = tiny_lm(0)
    draft, dv = tiny_lm(1, embed=16, layers=1)   # the cheap proposer

    batcher = ContinuousBatcher(
        target, tv, max_slots=4,
        paged=True, page_size=8,                 # pay-per-page KV
        draft_model=draft, draft_variables=dv, gamma=3,
    ).start()
    try:
        system_prompt = list(range(10, 29))      # 19 ids -> 2 shared pages
        handle = batcher.register_prefix(system_prompt)
        rec = batcher._prefixes[handle]
        print(f"system prompt: {len(system_prompt)} tokens -> "
              f"{rec['shared']} shared pages (prefilled once)")

        user_turns = [[40, 41], [50], [], [60, 61, 62]]
        streams = [batcher.submit(turn, max_new_tokens=8, prefix=handle)
                   for turn in user_turns]
        for turn, stream in zip(user_turns, streams):
            toks = stream.tokens()
            full = system_prompt + turn
            ref = np.asarray(generate(target, tv, jnp.asarray(full)[None],
                                      8))[0, len(full):].tolist()
            assert toks == ref, (turn, toks, ref)
            print(f"  user={turn}: completion {toks} (== target greedy)")

        batcher.release_prefix(handle)
        assert sorted(batcher._free) == list(range(1, batcher._np))
        print("released: every page back in the pool")
    finally:
        batcher.stop()
    print("system-prompt serving: shared-prefix + paged + speculative, "
          "all streams exact ok")


if __name__ == "__main__":
    main()
