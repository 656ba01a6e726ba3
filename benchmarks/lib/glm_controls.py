"""Controls for `drivers/glm_train.verify`: the trained program with one
deliberate fault, so that anybody can see the comparison refuse it.

    GLM_CONTROL=<name> python3 benchmarks/run.py --workload glm-train-moe ...

arms the named fault before the model is built; the whole run (warm-up,
window, `verify`) is then made by the faulty program, and its last line
has to come out `"correct": false`.

    mtp_off        the multi-token-prediction term is left out of the
                   loss (its weight 0): the module's weights and eh_proj
                   get no gradient
    no_renorm      `norm_topk_prob` is ignored: the chosen scores are not
                   divided by their sum
    bias_weighs    the selection bias weighs as well as chooses: the
                   weights are the BIASED scores of the chosen
    scores_softmax the router's scores are a softmax over the 64 outputs,
                   not a sigmoid of each
    experts_bf16   the routed experts' forward matmuls sum their 128-deep
                   passes in a bfloat16 accumulator (the precision below
                   the float32 accumulation the configuration states)
    dw_bf16_accum  an expert's dW sums its row tiles' products in a
                   bfloat16 accumulator
    state_frozen   the optimizer's learning rate and the controller's
                   gamma are 0 in the program (the reference keeps the
                   stated ones): every step leaves the state as it was

A fault is planted from here: a field of the routed layer, a function of
`ops/grouped_matmul.py` replaced by a faulty stand-in, one line of a
module's own source changed in a copy (`_mutated`), or a parameter of
the cell.  The program carries no hook for any of them.
"""
from __future__ import annotations


def _layer_with(**override):
    """Every routed layer the model builds takes these fields instead."""
    from mmlspark_tpu.models import glm_moe_lm

    sound = glm_moe_lm._SparseMLP
    glm_moe_lm._SparseMLP = lambda **kw: sound(**{**kw, **override})


def _mtp_off(env):
    env.params["mtp_loss_weight"] = 0.0


def _no_renorm(env):
    _layer_with(renormalise=False)


def _scores_softmax(env):
    _layer_with(scores="softmax")


def _mutated(cls, sound: str, faulty: str):
    """A copy of the class `cls` made from its own source with the one
    occurrence of `sound` replaced by `faulty`."""
    import inspect
    import sys
    import textwrap

    source = textwrap.dedent(inspect.getsource(cls))
    if source.count(sound) != 1:
        raise SystemExit(f"{cls.__name__}: {sound!r} is not there once; "
                         "the control has to follow the program")
    names = dict(vars(sys.modules[cls.__module__]))
    exec(compile(source.replace(sound, faulty),
                 f"<{cls.__name__} with a fault>", "exec"), names)
    return names[cls.__name__]


def _bias_weighs(env):
    from mmlspark_tpu.models import glm_moe_lm

    glm_moe_lm._SparseMLP = _mutated(
        glm_moe_lm._SparseMLP, "top_p = jnp.take_along_axis(p, top_e, -1)",
        "top_p = _biased")


def _experts_bf16(env):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops import grouped_matmul as gm

    def summed_in_bf16(product, rows, cols, depth):
        """128-deep passes of the MXU summed in a bf16 accumulator."""
        acc = jnp.zeros((rows, cols), jnp.bfloat16)
        for c in range(0, depth, 128):
            acc = (acc.astype(jnp.float32) + product(c)).astype(jnp.bfloat16)
        return acc.astype(jnp.float32)

    def tile_dot(x, w_ref):
        return summed_in_bf16(
            lambda c: jnp.dot(x[:, c:c + 128], w_ref[0, c:c + 128],
                              preferred_element_type=jnp.float32),
            x.shape[0], w_ref.shape[2], x.shape[1])

    def ragged(rows, w_gate, w_up, w_down, group_sizes):
        # off the TPU (the rehearsal's trained steps): the same accumulator
        def rd(a, w):
            return summed_in_bf16(
                lambda c: jax.lax.ragged_dot(
                    a[:, c:c + 128], w[:, c:c + 128], group_sizes,
                    preferred_element_type=jnp.float32),
                a.shape[0], w.shape[2], a.shape[1])

        h = (jax.nn.silu(rd(rows, w_gate)) * rd(rows, w_up)).astype(
            rows.dtype)
        return rd(h, w_down).astype(rows.dtype)

    gm._tile_dot, gm._moe_ragged = tile_dot, ragged


def _dw_bf16_accum(env):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops import grouped_matmul as gm

    def dw_call(x, dy, tile_expert, n_tiles, counts, tm, out_dtype):
        """`_gmm_dw_call`'s sum in its order, a row tile a step in plain
        XLA, each expert's accumulator bfloat16."""
        def tile(acc, t):
            rows = [jax.lax.dynamic_slice_in_dim(a, t * tm, tm)
                    for a in (x, dy)]
            prod = jax.lax.dot_general(
                *rows, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            e = tile_expert[t]
            summed = (acc[e].astype(jnp.float32) + prod).astype(acc.dtype)
            return acc.at[e].set(
                jnp.where(t < n_tiles[0], summed, acc[e])), None

        acc = jnp.zeros((counts.shape[0], x.shape[1], dy.shape[1]),
                        jnp.bfloat16)
        return jax.lax.scan(tile, acc, jnp.arange(x.shape[0] // tm))[
            0].astype(out_dtype)

    gm._gmm_dw_call = dw_call


def _state_frozen(env):
    env.params["learning_rate"] = 0.0
    env.params["bias_update_rate"] = 0.0


CONTROLS = {"mtp_off": _mtp_off, "no_renorm": _no_renorm,
            "bias_weighs": _bias_weighs, "scores_softmax": _scores_softmax,
            "experts_bf16": _experts_bf16, "dw_bf16_accum": _dw_bf16_accum,
            "state_frozen": _state_frozen}


def arm(name: str, env) -> None:
    """Put the named fault into the program's modules (or the cell's
    parameters).  Before the model is built."""
    if name not in CONTROLS:
        raise SystemExit(f"GLM_CONTROL={name!r}: not one of "
                         f"{sorted(CONTROLS)}")
    CONTROLS[name](env)
