"""On-chip ablation of the LM train step (bench.py's _measure_transformer
workload): attributes the gap between measured step time and the FLOPs
lower bound.  Each config prints one JSON line
{tag, tokens_per_sec, mfu, ms_per_step}.

Timing note: every measurement here blocks on an actual device->host
fetch of the loss vector (np.asarray), the same thing a real training
loop reads.  A CPU run (LM_ABLATE_SMOKE) checks the code path only: its
`mfu` is null, never a v5e share of a CPU time.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
import optax

from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.models.training import make_lm_train_epoch
from mmlspark_tpu.parallel.ring_attention import full_attention


def peak_flops():
    return 197e12  # v5e bf16


def _time_epoch(run_fetch, reps=3):
    run_fetch()  # warm (drains the dispatch queue too)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run_fetch()
        best = min(best, time.perf_counter() - t0)
    return best


def measure(tag, batch=16, seq=1024, steps=8, attn_fn=None, fwd_only=False,
            num_heads=12):
    smoke = bool(os.environ.get("LM_ABLATE_SMOKE"))
    if smoke:
        # CPU contract smoke (tests/test_sweep_contract.py): the same
        # code path — model build, scanned epoch, fetch-blocked timing,
        # JSON shape — at a size the CPU backend can turn around (batch
        # 8 divides the virtual 8-device data mesh the test env pins)
        batch, seq, steps, vocab = 8, 128, 2, 64
        model = transformer_lm(vocab_size=vocab, embed_dim=64,
                               num_layers=1, num_heads=1, max_len=seq,
                               dtype=jnp.float32, attn_fn=attn_fn)
    else:
        vocab = 8192
        model = transformer_lm(vocab_size=vocab, embed_dim=768,
                               num_layers=12, num_heads=num_heads,
                               max_len=seq, dtype=jnp.bfloat16,
                               attn_fn=attn_fn)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (steps, batch, seq), 0, vocab, jnp.int32)
    params = jax.jit(lambda r, t: model.init(r, t)["params"])(rng, tokens[0])
    if fwd_only:
        def fwd_epoch(params, tokens):
            def body(_, toks):
                logits, _ = model.apply({"params": params}, toks)
                lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
                ll = jnp.take_along_axis(lp, toks[:, 1:][..., None], axis=-1)
                return None, -jnp.mean(ll)
            _, losses = jax.lax.scan(body, None, tokens)
            return losses
        compiled = jax.jit(fwd_epoch).lower(params, tokens).compile()
        run = lambda: np.asarray(compiled(params, tokens))
        flops_step = 0.0
    else:
        opt = optax.adam(3e-4)
        opt_state = jax.jit(opt.init)(params)
        epoch = make_lm_train_epoch(model, opt, donate=False)
        # the COMPILED analysis: Lowered.cost_analysis() is None on TPU
        flops_step = float(epoch.lower(params, opt_state, tokens[:1])
                           .compile().cost_analysis()["flops"])
        compiled = epoch.lower(params, opt_state, tokens).compile()
        run = lambda: np.asarray(compiled(params, opt_state, tokens)[2])
    best = _time_epoch(run)
    print(json.dumps({
        **({"smoke": True} if smoke else {}),
        "tag": tag,
        "tokens_per_sec": round(steps * batch * seq / best, 0),
        "mfu": (round(steps * flops_step / best / peak_flops(), 4)
                if flops_step and not smoke else None),
        "ms_per_step": round(best / steps * 1e3, 2),
        "flops_step_tf": round(flops_step / 1e12, 2),
    }), flush=True)


def main():
    xla_attn = lambda q, k, v: full_attention(q, k, v, causal=True)
    measure("baseline_b16")
    measure("fwd_only_b16", fwd_only=True)
    measure("xla_attn_b16", attn_fn=xla_attn)
    measure("b32", batch=32)
    # attention as identity (v passthrough): the gap between this and
    # baseline is the TOTAL attention cost (kernel + projections' fusion
    # slack) — the model still type-checks because attn_fn sees [B,H,S,D]
    measure("no_attn_b16", attn_fn=lambda q, k, v: v)
    # same 768 width, 6 heads of d128: whether the d_head=64 shape (half
    # the 128-lane register width) is what holds the fused kernel back
    measure("h6_d128_b16", num_heads=6)


if __name__ == "__main__":
    main()
