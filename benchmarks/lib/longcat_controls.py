"""Controls for `drivers/longcat_serve.verify`: the served program with
one deliberate fault, so that anybody can see the comparison refuse it.

    LONGCAT_CONTROL=<name> python3 benchmarks/run.py --workload longcat-serve-long ...

arms the named fault before the programs are built; the whole run (warm-
up, window, `verify`) is then made by the faulty program, and its last
line has to come out `"correct": false`.  All but the last sit in the
decode step only (one token a slot): the admission programs are traced as
they are, so a sound run's compile cache serves them again and a control
costs one program's compile.  The last sits in the admission programs
only, which all compile anew.

    zero_off       the identity (zero-compute) experts' term is left out
                   of the routed sum: a third of the assignments add
                   nothing
    kv_scale_off   `mla_scale_kv_lora` is left out: the decode step's
                   new rows enter the cache sqrt(6144 / 512) too small
    absorb_bf16    the absorbed query q' = q_nope . Wkvb_K meets the
                   cached rows rounded to bfloat16 (the precision below
                   the float32 accumulation the configuration states:
                   the unabsorbed product sums in float32)
    experts_bf16   the routed experts' matmuls accumulate in bfloat16:
                   `lib/laguna_controls.py`'s fault of that name, the
                   grouped matmul being both models'
    prefill_experts_bf16
                   the same fault in the admissions' routed layer (the
                   128-row tiles, the chunked dispatch) and nowhere in
                   the decode step
"""
from __future__ import annotations

import contextlib


def _in_program(which: int, patch):
    """Trace the decode step (`which` 0) or the admission forward (1), at
    its first call of each shape, inside `patch()`, a context manager that
    puts the fault into the program's modules; the other program is traced
    as it is."""
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    programs = ContinuousBatcher._own_programs

    def own_programs(self, taps):
        made = list(programs(self, taps))
        sound = made[which]

        def faulty(*args):
            with patch():
                return sound(*args)

        made[which] = faulty
        return tuple(made)

    ContinuousBatcher._own_programs = own_programs


def _in_decode_step(patch):
    _in_program(0, patch)


@contextlib.contextmanager
def _swapped(module, name, value):
    sound = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, sound)


def _zero_off():
    import jax.numpy as jnp

    from mmlspark_tpu.models import moe_lm

    _in_decode_step(lambda: _swapped(
        moe_lm, "_zero_experts_term",
        lambda y, weights, top_e, num_experts: jnp.zeros(y.shape,
                                                         jnp.float32)))


def _kv_scale_off():
    from mmlspark_tpu.models import longcat_lm

    _in_decode_step(lambda: _swapped(
        longcat_lm, "_kv_gain", lambda embed_dim, kv_rank: 1.0))


def _absorb_bf16():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops import paged_attention as pa

    def parts(q_abs, dtype):
        hi = jax.lax.reduce_precision(q_abs, 8, 7).astype(dtype)
        return hi, jnp.zeros_like(hi)

    # the page walk is a decode step's alone: no admission reads it
    pa._mla_query_parts = parts


def _experts_bf16():
    from lib import laguna_controls

    laguna_controls.CONTROLS["experts_bf16"]()


def _prefill_experts_bf16():
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
        # off the TPU (the rehearsal): the same accumulator
        def rd(a, w):
            return summed_in_bf16(
                lambda c: jax.lax.ragged_dot(
                    a[:, c:c + 128], w[:, c:c + 128], group_sizes,
                    preferred_element_type=jnp.float32),
                a.shape[0], w.shape[2], a.shape[1])

        h = (jax.nn.silu(rd(rows, w_gate)) * rd(rows, w_up)).astype(
            rows.dtype)
        return rd(h, w_down).astype(rows.dtype)

    @contextlib.contextmanager
    def patch():
        with _swapped(gm, "_tile_dot", tile_dot), \
                _swapped(gm, "_moe_ragged", ragged):
            yield

    # on the chip an admission's rows go through `_moe_gmm_prefill` (256
    # tokens or more a program) and a decode step's through
    # `_moe_gmm_decode`: the two named wrappers keep their traces apart
    _in_program(1, patch)


CONTROLS = {"zero_off": _zero_off, "kv_scale_off": _kv_scale_off,
            "absorb_bf16": _absorb_bf16, "experts_bf16": _experts_bf16,
            "prefill_experts_bf16": _prefill_experts_bf16}


def arm(name: str) -> None:
    """Put the named fault into the program's modules.  Before any
    program is traced."""
    if name not in CONTROLS:
        raise SystemExit(f"LONGCAT_CONTROL={name!r}: not one of "
                         f"{sorted(CONTROLS)}")
    CONTROLS[name]()
