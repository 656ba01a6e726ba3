"""Training across four chips: `make_lm_train_step_3d` on the mesh the
configuration's deployment states (`MeshPlan(data, model, pipe)`), steps
back to back.

One unit of work is one optimizer step over tokens `[A, M, mb, S]`
(gradient accumulation x microbatches x sequences), timed on the host
clock to `block_until_ready`.  The reference is the plain one-device f32
forward of lib/reference.py at the initial weights over the whole first
step's sequences, two at a time.
"""
from __future__ import annotations

import time

# bf16 compute under GSPMD against an f32 reference on the same 32
# sequences (equal microbatches, so the step's mean of means is the
# global mean): see drivers/lm_train.py for the sizes of the errors
LOSS_ABS_TOL = 2e-3


def setup(env) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from lib.lm import build_lm
    from mmlspark_tpu.models.training import (lm_params_to_3d,
                                              make_lm_train_step_3d,
                                              shard_params)
    from mmlspark_tpu.parallel.mesh import MeshContext, MeshPlan, make_mesh
    from mmlspark_tpu.parallel.sharding_rules import lm_3d_rules

    seq = env.traffic["seq_len"]
    s, model = build_lm(env.config, seq)
    a, m, mb = (env.params[k] for k in ("accum", "micro", "mb"))
    mesh_shape = env.config["deployment"]["mesh"]
    plan = MeshPlan(devices=list(env.devices), **mesh_shape)
    one = make_mesh(devices=list(env.devices)[:1])
    rng = jax.random.PRNGKey(env.seed)
    tokens = np.asarray(jax.random.randint(
        rng, (a, m, mb, seq), 0, env.config["vocab_size"], jnp.int32))
    with MeshContext(one):
        init = jax.jit(lambda r: lm_params_to_3d(
            model.init(r, jnp.zeros((1, seq), jnp.int32))["params"],
            s["layers"], mesh_shape["pipe"]))
        p1 = init(rng)
    opt = optax.adam(env.params["learning_rate"])
    st = {"model": model, "plan": plan, "one": one, "init": init, "rng": rng,
          "tokens": tokens, "losses": []}
    with MeshContext(plan.mesh):
        p3 = shard_params(p1, plan.mesh, lm_3d_rules())
        del p1
        # eager: zeros_like keeps each parameter's sharding, a jitted init
        # would hand back replicated moments (6.7 GB a chip)
        o3 = opt.init(p3)
        step = make_lm_train_step_3d(model, opt, plan,
                                     remat=env.params["remat"])
        # the step leaves its outputs' shardings to the compiler, so its
        # second call compiles again: both programs are warmed here
        for _ in range(env.params["warm_steps"]):
            p3, o3, metrics = step(p3, o3, tokens)
            st["losses"].append(float(metrics["loss"]))
    st.update(step=step, p3=p3, o3=o3, first_loss=st["losses"][0])
    env.log({"line": "step_programs",
             "compiled": getattr(step, "_cache_size", lambda: None)()})
    return st


def measure(env, st) -> dict:
    import jax
    import numpy as np

    from mmlspark_tpu.parallel.mesh import MeshContext

    tokens = st["tokens"]
    done = {"steps": 0}
    env.slice.open_window(lambda: done)
    p3, o3 = st.pop("p3"), st.pop("o3")
    work_s, losses = 0.0, []
    deadline = time.monotonic() + env.seconds
    with MeshContext(st["plan"].mesh):
        while time.monotonic() < deadline:
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.step"):
                p3, o3, metrics = st["step"](p3, o3, tokens)
                loss = float(jax.block_until_ready(metrics["loss"]))
            work_s += time.monotonic() - t0
            losses.append(loss)
            done["steps"] += 1
            env.slice.poll()
    del p3, o3
    st["losses"].extend(losses)
    bad = int((~np.isfinite(np.asarray(losses))).sum())
    return {"attempted": len(losses), "failed": bad,
            "counters": {"steps": float(len(losses)),
                         "tokens": float(len(losses) * tokens.size),
                         "window_s": work_s},
            "notes": {"step_s_mean": work_s / len(losses),
                      "loss_first": losses[0], "loss_last": losses[-1]}}


def verify(env, st, measured) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib.flops import lm_sizes
    from lib.reference import lm_loss
    from mmlspark_tpu.models.training import lm_params_from_3d
    from mmlspark_tpu.parallel.mesh import MeshContext

    s = lm_sizes(env.config)
    with MeshContext(st["one"]):
        params = jax.jit(lambda r: lm_params_from_3d(
            st["init"](r), s["layers"]))(st["rng"])
        loss = jax.jit(lambda p, t: lm_loss(p, t, s["layers"], s["heads"]))
        first = jnp.asarray(st["tokens"].reshape(-1, st["tokens"].shape[-1]))
        ref = float(np.mean([float(loss(params, first[i:i + 2]))
                             for i in range(0, first.shape[0], 2)]))
    diff = abs(st["first_loss"] - ref)
    finite = bool(np.isfinite(np.asarray(st["losses"])).all())
    return {"correct": bool(finite and diff <= LOSS_ABS_TOL
                            and measured["failed"] == 0),
            "compared": "first step's loss vs the plain one-device f32 "
                        "forward at the initial weights on the same "
                        "sequences (abs diff)",
            "max_diff": diff, "tol": LOSS_ABS_TOL,
            "first_loss": st["first_loss"], "ref_first_loss": ref}


def close(st) -> None:
    pass
