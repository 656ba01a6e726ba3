"""Pipeline parallelism: a GPipe schedule as shard_map + ppermute.

The last of the mesh parallelisms (dp/tp/sp/ep live elsewhere): P pipeline
stages hold their own slice of a stacked parameter pytree (leading dim P,
sharded over a mesh axis), microbatches stream through the stage chain
with activations hopping stage-to-stage over `ppermute` — the classic
bubble schedule (M + P - 1 steps for M microbatches; bubble fraction
(P-1)/(M+P-1)).

TPU-first shape: ONE jitted program — the schedule is a `lax.scan`, the
inter-stage hop is a collective XLA lowers onto ICI, and the whole thing
is differentiable (ppermute transposes to the reverse hop), so training
backprops through the pipe with no custom VJP.

The reference has no model parallelism of any kind (SURVEY §2.10 last
row); this is beyond-reference infrastructure shaped by the same
mesh/collective design as the rest of `parallel/`.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import shard_map

__all__ = ["pipeline_apply", "gpipe_spmd_apply", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """[pytree per stage] -> one pytree with leading dim P (stage axis) —
    the layout `pipeline_apply` shards over the mesh axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_apply(stage_fn: Callable, stacked_params, x: jnp.ndarray,
                   mesh: Mesh, axis: str = "model") -> jnp.ndarray:
    """Run `x [M, mb, ...]` microbatches through P chained stages.

    stage_fn(params_i, x) -> same-shaped activation; `stacked_params` has
    leading dim P == mesh.shape[axis], sharded so stage i's weights live
    on pipe rank i.  Returns [M, mb, ...] outputs (replicated), equal to
    applying the P stages sequentially to each microbatch.
    """
    n_stages = mesh.shape[axis]
    leading = {a.shape[0] for a in jax.tree.leaves(stacked_params)}
    if leading != {n_stages}:
        raise ValueError(
            f"stacked_params leading dim(s) {sorted(leading)} must equal "
            f"mesh axis {axis!r} size {n_stages} — one stage per pipe "
            "rank (a clean multiple would silently run every k-th stage)")
    m = x.shape[0]
    steps = m + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params_local, xs):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        rank = jax.lax.axis_index(axis)
        # the carry becomes device-varying after the first ppermute; the
        # zero init must carry the same varying-axes type
        buf = jax.lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")

        def body(buf, t):
            # stage 0 ingests microbatch t (while any remain); downstream
            # stages consume what the previous stage ppermuted to them
            inp = jnp.where(rank == 0,
                            xs[jnp.clip(t, 0, m - 1)], buf)
            out = stage_fn(params_local, inp)
            nxt = jax.lax.ppermute(out, axis, perm)
            # the LAST stage's output at step t is microbatch t-(P-1)
            return nxt, out

        _, outs = jax.lax.scan(body, buf, jnp.arange(steps))
        # outs [steps, mb, ...]: keep the last stage's valid window and
        # replicate it to every rank (other ranks contribute zeros)
        window = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, m, axis=0)
        mine = jnp.where(rank == n_stages - 1, window, 0)
        return jax.lax.psum(mine, axis)

    spec_p = jax.tree.map(lambda _: P(axis), stacked_params)
    return shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_p, P()), out_specs=P(),
    )(stacked_params, x)


def gpipe_spmd_apply(stage_fn: Callable, stacked_params, x: jnp.ndarray,
                     mesh: Mesh = None, axis: str = "pipe",
                     batch_axis: Optional[str] = "data") -> jnp.ndarray:
    """The SAME M + P - 1 GPipe schedule as :func:`pipeline_apply`,
    lowered through GSPMD sharding annotations instead of shard_map —
    which is what lets it COMPOSE with data-parallel batch sharding and
    megatron tensor rules on one 3D mesh (shard_map bodies see local
    arrays; tensor-parallel collectives inside them would have to be
    hand-written).

    ``x [M, mb, ...]`` microbatches, mb pinned to ``batch_axis`` at every
    tick (None where the caller shards the batch itself: the 3D step
    vmaps this whole schedule over 'data', and an axis may appear in a
    spec once); ``stacked_params`` leaves carry a
    leading stage dim P (any further leading dims — e.g. the
    [P, K_blocks] layout of ``lm_params_to_3d`` — are stage-private).
    The schedule is a `lax.scan` whose donated carry is the [P, mb, ...]
    activation buffer: each tick runs every stage in parallel
    (``jax.vmap`` over the stage dim, which XLA partitions over the pipe
    axis), then the buffer rolls one stage forward — `jnp.roll` on a
    pipe-sharded dim lowers to the same collective-permute hop
    pipeline_apply issues by hand.  Differentiable end to end; returns
    [M, mb, ...] equal to applying the stages sequentially.
    """
    leading = {a.shape[0] for a in jax.tree.leaves(stacked_params)}
    if len(leading) != 1:
        raise ValueError(
            f"stacked_params leading dims differ: {sorted(leading)}")
    (p,) = leading
    if mesh is not None and axis in mesh.shape and mesh.shape[axis] != p:
        raise ValueError(
            f"stacked_params leading dim {p} != mesh axis {axis!r} size "
            f"{mesh.shape[axis]} — one stage per pipe rank")
    m = x.shape[0]
    steps = m + p - 1
    vstage = jax.vmap(stage_fn)

    def pin(buf):
        # keep the buffer stage-dim on the pipe axis and the microbatch
        # dim on batch_axis at every tick, so the roll stays a pure
        # neighbor hop instead of a resharding
        if mesh is None:
            return buf
        return jax.lax.with_sharding_constraint(
            buf, NamedSharding(mesh, P(axis, batch_axis)))

    def body(buf, t):
        # stage 0 ingests microbatch t (clamped: drain ticks feed a dead
        # row that ys slicing discards); stages 1..P-1 consume what the
        # previous tick rolled to them
        inp = jax.lax.dynamic_index_in_dim(x, jnp.clip(t, 0, m - 1), 0,
                                           keepdims=False)
        buf = jax.lax.dynamic_update_index_in_dim(
            buf, inp.astype(buf.dtype), 0, 0)
        out = pin(vstage(stacked_params, buf))
        # the LAST stage's output at tick t is microbatch t - (P-1)
        y = out[p - 1]
        return pin(jnp.roll(out, 1, axis=0)), y

    buf0 = pin(jnp.zeros((p,) + x.shape[1:], x.dtype))
    _, ys = jax.lax.scan(body, buf0, jnp.arange(steps))
    return ys[p - 1:]
