"""Training on one chip: `make_lm_train_epoch`, epochs of scanned
optimizer steps dispatched back to back.

One unit of work is one epoch (`steps_per_epoch` Adam steps in ONE jitted
scan), timed on the host clock to `block_until_ready`.  The reference is
the plain f32 forward of lib/reference.py at the initial weights, on the
first step's whole batch, two sequences at a time.
"""
from __future__ import annotations

import time

# bf16 compute against an f32 reference, a mean over 6,000 tokens of a
# loss near ln(vocab) = 10.8: 1.1e-4 on the chip (PR 22), so 2e-3 leaves
# rounding twenty times its size and still refuses a wrong mask, a dropped
# layer, or matmuls in a type coarser than bf16
LOSS_ABS_TOL = 2e-3


def build(env):
    """(model, init(rng) -> params, rng, tokens [steps, batch, seq])."""
    import jax
    import jax.numpy as jnp

    from lib.lm import build_lm

    seq = env.traffic["seq_len"]
    _sizes, model = build_lm(env.config, seq)
    rng = jax.random.PRNGKey(env.seed)
    # uniform over the PUBLISHED vocabulary: a padding row is never a target
    tokens = jax.random.randint(
        rng, (env.params["steps_per_epoch"], env.params["batch"], seq),
        0, env.config["vocab_size"], jnp.int32)
    init = jax.jit(lambda r: model.init(r, tokens[0, :1])["params"])
    return model, init, rng, tokens


def setup(env) -> dict:
    import jax
    import numpy as np
    import optax

    from mmlspark_tpu.models.training import make_lm_train_epoch

    model, init, rng, tokens = build(env)
    params = init(rng)
    opt = optax.adam(env.params["learning_rate"])
    opt_state = jax.jit(opt.init)(params)
    epoch = make_lm_train_epoch(model, opt)
    st = {"model": model, "init": init, "rng": rng, "tokens": tokens,
          "epoch": epoch, "losses": []}
    for _ in range(env.params["warm_epochs"]):
        params, opt_state, losses = epoch(params, opt_state, tokens)
        st["losses"].append(np.asarray(losses))
    st["params"], st["opt_state"] = params, opt_state
    st["first_loss"] = float(st["losses"][0][0])
    return st


def measure(env, st) -> dict:
    import jax
    import numpy as np

    steps, batch, seq = st["tokens"].shape
    done = {"steps": 0}
    env.slice.open_window(lambda: done)
    params, opt_state = st.pop("params"), st.pop("opt_state")
    work_s, losses = 0.0, []
    deadline = time.monotonic() + env.seconds
    while time.monotonic() < deadline:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.epoch"):
            params, opt_state, out = st["epoch"](params, opt_state,
                                                 st["tokens"])
            out = np.asarray(jax.block_until_ready(out))
        work_s += time.monotonic() - t0
        losses.append(out)
        done["steps"] += steps
        env.slice.poll()
    del params, opt_state
    losses = np.concatenate(losses)
    st["losses"].append(losses)
    bad = int((~np.isfinite(losses)).sum())
    return {"attempted": int(losses.size), "failed": bad,
            "counters": {"steps": float(losses.size),
                         "tokens": float(losses.size * batch * seq),
                         "window_s": work_s},
            "notes": {"step_s_mean": work_s / losses.size,
                      "loss_first": float(losses[0]),
                      "loss_last": float(losses[-1])}}


def reference_first_loss(env, st) -> float:
    """The plain forward at the initial weights (the same jitted init from
    the same seed), over the first step's batch, two sequences at a time."""
    import jax
    import numpy as np

    from lib.flops import lm_sizes
    from lib.reference import lm_loss

    s = lm_sizes(env.config)
    params = st["init"](st["rng"])
    loss = jax.jit(lambda p, t: lm_loss(p, t, s["layers"], s["heads"]))
    first = st["tokens"][0]
    parts = [float(loss(params, first[i:i + 2]))
             for i in range(0, first.shape[0], 2)]
    return float(np.mean(parts))


def verify(env, st, measured) -> dict:
    import numpy as np

    ref = reference_first_loss(env, st)
    diff = abs(st["first_loss"] - ref)
    finite = all(bool(np.isfinite(x).all()) for x in st["losses"])
    return {"correct": bool(finite and diff <= LOSS_ABS_TOL
                            and measured["failed"] == 0),
            "compared": "first step's loss vs the plain f32 forward at the "
                        "initial weights on the same batch (abs diff)",
            "max_diff": diff, "tol": LOSS_ABS_TOL,
            "first_loss": st["first_loss"], "ref_first_loss": ref}


def close(st) -> None:
    pass
