"""Controls for `drivers/laguna_serve.verify`: the served program with one
deliberate fault, so that anybody can see the comparison refuse it.

    LAGUNA_CONTROL=<name> python3 benchmarks/run.py --workload laguna-serve-mixed ...

arms the named fault before the programs are built; the whole run (warm-
up, window, `verify`) is then made by the faulty program, and its last
line has to come out `"correct": false`.  Each fault sits in the decode
step only (one token a slot); where it can be put there without another
frame in the admission programs' source locations (`router_bf16`,
`window_short`), a sound run's compile cache serves those again and the
control costs one program's compile.

    experts_bf16   the routed experts' matmuls accumulate in bfloat16:
                   128-deep passes of the MXU summed in a bf16 accumulator
                   (the precision below the float32 accumulation stated)
    router_bf16    router logits from a bfloat16 matmul, rounded to
                   bfloat16 (the configuration states float32)
    window_short   the decode attention of window layers sees one page
                   fewer than the window (an indexing fault, for the
                   limits on the served tokens)
"""
from __future__ import annotations


def _experts_bf16():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops import grouped_matmul as gm

    sound = gm._tile_dot

    def tile_dot(x, w_ref):
        if x.shape[0] != gm._DECODE_TILE:
            return sound(x, w_ref)
        acc = jnp.zeros((x.shape[0], w_ref.shape[2]), jnp.bfloat16)
        for c in range(0, x.shape[1], 128):
            acc = (acc.astype(jnp.float32) + jnp.dot(
                x[:, c:c + 128], w_ref[0, c:c + 128],
                preferred_element_type=jnp.float32)).astype(jnp.bfloat16)
        return acc.astype(jnp.float32)

    def ragged(rows, w_gate, w_up, w_down, group_sizes):
        # off the TPU (the rehearsal): the same accumulator, every call
        def rd(a, w):
            acc = jnp.zeros((a.shape[0], w.shape[2]), jnp.bfloat16)
            for c in range(0, a.shape[1], 128):
                acc = (acc.astype(jnp.float32) + jax.lax.ragged_dot(
                    a[:, c:c + 128], w[:, c:c + 128], group_sizes,
                    preferred_element_type=jnp.float32)
                ).astype(jnp.bfloat16)
            return acc.astype(jnp.float32)

        h = (jax.nn.silu(rd(rows, w_gate)) * rd(rows, w_up)).astype(
            rows.dtype)
        return rd(h, w_down).astype(rows.dtype)

    gm._tile_dot, gm._moe_ragged = tile_dot, ragged


def _router_bf16():
    import jax.numpy as jnp

    from mmlspark_tpu.models import moe_lm
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    sound, programs = moe_lm._router_logits, ContinuousBatcher._own_programs

    def router_logits(y, wr):
        return jnp.dot(y.astype(jnp.bfloat16), wr.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16
                       ).astype(jnp.float32)

    def own_programs(self, taps):
        # the decode step is traced (at its first call) with the faulty
        # router in the model's module, the admission forward as it is
        step, prefill = programs(self, taps)

        def faulty_step(*args):
            moe_lm._router_logits = router_logits
            try:
                return step(*args)
            finally:
                moe_lm._router_logits = sound

        return faulty_step, prefill

    ContinuousBatcher._own_programs = own_programs


def _window_short():
    from mmlspark_tpu.ops import paged_attention as pa

    kernel, gather = pa.paged_decode_attention, pa._xla_paged_window

    def short(window, k_pool):
        return window - k_pool.shape[1]

    def paged_decode_attention(q, k_pool, v_pool, page_table, pos,
                               window=None):
        return kernel(q, k_pool, v_pool, page_table, pos,
                      window=None if window is None
                      else short(window, k_pool))

    def xla_paged_window(q, k_pool, v_pool, page_table, pos, window):
        return gather(q, k_pool, v_pool, page_table, pos,
                      short(window, k_pool))

    pa.paged_decode_attention = paged_decode_attention
    pa._xla_paged_window = xla_paged_window


CONTROLS = {"experts_bf16": _experts_bf16, "router_bf16": _router_bf16,
            "window_short": _window_short}


def arm(name: str) -> None:
    """Put the named fault into the program's modules.  Before any
    program is traced."""
    if name not in CONTROLS:
        raise SystemExit(f"LAGUNA_CONTROL={name!r}: not one of "
                         f"{sorted(CONTROLS)}")
    CONTROLS[name]()
