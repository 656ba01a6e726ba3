"""Sharded training step factory: data/tensor-parallel fine-tuning on a mesh.

Replaces the reference's transfer-learning training path (ImageFeaturizer ->
new head, DeepLearning Flower notebook) with pjit-sharded SGD: batch sharded
over the mesh 'data' axis, large head kernels shardable over 'model', psum
handled by XLA from sharding annotations.  bfloat16 compute, float32 state.
"""
from __future__ import annotations

import itertools
import math
import os
import time
import warnings
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import telemetry as core_telemetry
from ..parallel.mesh import batch_sharding, default_mesh, replicated_sharding
from ..parallel.sharding_rules import (make_shard_and_gather_fns,
                                       match_partition_rules)

__all__ = ["TrainState", "make_train_step", "make_train_epoch",
           "make_lm_train_epoch", "record_lm_stats", "make_distill_epoch",
           "make_eval_step",
           "make_lm_train_step_3d", "lm_params_to_3d", "lm_params_from_3d",
           "make_lm_resumable_step_3d",
           "fit_epochs", "fit_epochs_resumable", "shard_params",
           "scan_slice_steps"]

# device-memory budget for one scanned slice of training data; a full
# epoch is scanned in slices of at most this many bytes so device memory
# stays O(slice), not O(dataset)
SCAN_SLICE_BYTES = 256 * 1024 * 1024


def scan_slice_steps(n_steps: int, bytes_per_step: int,
                     budget: int = SCAN_SLICE_BYTES) -> int:
    """How many steps of stacked minibatches fit one scanned dispatch."""
    return max(1, min(n_steps, budget // max(1, bytes_per_step)))


class TrainState:
    """Minimal pytree train state: params, batch_stats, opt_state, step."""

    def __init__(self, params, batch_stats, opt_state, step=0):
        self.params = params
        self.batch_stats = batch_stats
        self.opt_state = opt_state
        self.step = step

    def tree_flatten(self):
        return (self.params, self.batch_stats, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def shard_params(tree, mesh: Mesh, model_axis_rules=None):
    """Place a param tree on the mesh.  Default: replicate everything.

    ``model_axis_rules`` is a partition-rule TABLE — an ordered sequence
    of ``(regex, PartitionSpec)`` pairs matched first-wins against each
    leaf's ``/``-joined path name (parallel/sharding_rules.py) — or,
    legacy surface, a ``(path, arr) -> PartitionSpec`` callable."""
    if model_axis_rules is None:
        return jax.device_put(tree, replicated_sharding(mesh))
    if callable(model_axis_rules):
        def place(path, arr):
            spec = model_axis_rules(path, arr) or P()
            return jax.device_put(arr, NamedSharding(mesh, spec))

        return jax.tree_util.tree_map_with_path(place, tree)
    specs = match_partition_rules(model_axis_rules, tree)
    shard_fns, _ = make_shard_and_gather_fns(specs, mesh)
    return jax.tree.map(lambda f, x: f(x), shard_fns, tree)


def softmax_cross_entropy(logits, labels, num_classes):
    one_hot = jax.nn.one_hot(labels, num_classes)
    return optax.softmax_cross_entropy(logits, one_hot).mean()


def _step_body(model, optimizer, num_classes, seed: int = 0):
    """The un-jitted SGD step shared by make_train_step (one dispatch per
    step) and make_train_epoch (lax.scan over many steps in one dispatch)."""

    def step(state: TrainState, images, labels):
        # deterministic per-step dropout key (scan-safe: derived from the
        # traced step counter); models without dropout just ignore it, and
        # models without BatchNorm yield no 'batch_stats' updates
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), state.step)

        def loss_fn(params):
            (logits, _taps), updates = model.apply(
                {"params": params, "batch_stats": state.batch_stats},
                images,
                train=True,
                mutable=["batch_stats", "losses"],
                rngs={"dropout": rng},
            )
            loss = softmax_cross_entropy(logits, labels, num_classes)
            # module-sown auxiliary objectives (MoE load balance); dense
            # models sow nothing and the sum is 0
            aux = sum(jnp.sum(v) for v in
                      jax.tree.leaves(updates.get("losses", {})))
            loss = loss + 0.01 * aux
            return loss, (logits, updates.get("batch_stats",
                                              state.batch_stats))

        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
        # global grad-norm rides along as a health probe for the training
        # guard (models/guard.py): one scalar the step computes anyway-ish
        # (same reduction tree XLA fuses into the update), so non-finite
        # gradients are detectable without an extra dispatch
        return (
            TrainState(new_params, new_stats, new_opt, state.step + 1),
            {"loss": loss, "accuracy": acc,
             "grad_norm": optax.global_norm(grads)},
        )

    return step


def make_train_step(
    model,
    optimizer,
    num_classes: int,
    mesh: Optional[Mesh] = None,
    donate: bool = True,
    seed: int = 0,
):
    """Build `step(state, images, labels) -> (state, metrics)`, jitted with
    batch-sharded inputs.  `model.apply` must accept
    (variables, x, train=True, mutable=['batch_stats']).  `seed` varies the
    dropout mask stream (per-step keys are folded from it)."""
    mesh = mesh or default_mesh()
    step = _step_body(model, optimizer, num_classes, seed)
    img_sh = batch_sharding(mesh, 4)
    lbl_sh = batch_sharding(mesh, 1)
    return core_telemetry.watch_compiles(jax.jit(
        step,
        in_shardings=(None, img_sh, lbl_sh),
        donate_argnums=(0,) if donate else (),
    ), name="training.train_step")


def make_train_epoch(
    model,
    optimizer,
    num_classes: int,
    mesh: Optional[Mesh] = None,
    donate: bool = True,
    seed: int = 0,
):
    """Build `epoch(state, images, labels) -> (state, metrics)` running a
    whole stack of minibatches ([S, B, ...] / [S, B]) as ONE jitted
    `lax.scan` — one host dispatch for S optimizer steps, so per-call
    host latency never gates the train loop and XLA keeps state resident
    on device across steps.
    Metrics are per-step stacks ([S] arrays); batches stay sharded over the
    mesh 'data' axis (leading scan axis replicated)."""
    mesh = mesh or default_mesh()
    step = _step_body(model, optimizer, num_classes, seed)

    def epoch(state: TrainState, images, labels):
        def body(carry, batch):
            new_state, m = step(carry, batch[0], batch[1])
            return new_state, m

        return jax.lax.scan(body, state, (images, labels))

    img_sh = NamedSharding(mesh, P(None, "data"))
    lbl_sh = NamedSharding(mesh, P(None, "data"))
    return core_telemetry.watch_compiles(jax.jit(
        epoch,
        in_shardings=(None, img_sh, lbl_sh),
        donate_argnums=(0,) if donate else (),
    ), name="training.train_epoch")


def make_lm_train_epoch(
    model,
    optimizer,
    mesh: Optional[Mesh] = None,
    donate: bool = True,
):
    """`epoch(params, opt_state, tokens) -> (params, opt_state, losses)`:
    a whole stack of next-token minibatches ([S, B, seq] int32) as ONE
    jitted `lax.scan` — the TransformerLM counterpart of make_train_epoch
    (same reason: one dispatch per epoch keeps per-call host latency
    out of the loop; params/optimizer stay in HBM).

    The objective is the MODEL's: `model.lm_objective(variables, tokens)
    -> (loss, parts)`.  `TransformerLM`'s is mean next-token
    cross-entropy in f32 PLUS 0.01x any module-sown 'losses' terms (the
    MoE load-balance aux: MoE loss curves are not pure cross-entropy)
    and no parts; models/glm_moe_lm.py's has two loss terms, a head whose
    logits are never whole and the step's routing statistics.  The
    gradient and the optimizer see `variables["params"]` alone
    (`opt_state` is `optimizer.init` of that); whatever else the model
    keeps, the state no gradient owns, is moved after
    `optax.apply_updates` by `model.lm_controller(variables, parts)`.

    `params` is the bare parameter tree, and then what comes back is bare
    too and `losses` the per-step loss [S]; or the model's VARIABLES
    (`{"params": ..., ...}`), and then variables come back and `losses`
    is a dict of per-step stacks: `loss` and every scalar part
    (`record_lm_stats` turns the fetched ones into counters).
    `epoch.loss_and_grads(variables, tokens [B, S]) -> ((loss, parts),
    gradients)` is the function the scan body differentiates, for
    whoever wants to check the timed program's own gradients."""
    mesh = mesh or default_mesh()

    def loss_and_grads(variables, toks):
        return jax.value_and_grad(
            lambda p: model.lm_objective({**variables, "params": p}, toks),
            has_aux=True)(variables["params"])

    def step(variables, opt_state, toks):
        (loss, parts), grads = loss_and_grads(variables, toks)
        updates, opt_state = optimizer.update(grads, opt_state,
                                              variables["params"])
        variables = model.lm_controller(
            {**variables,
             "params": optax.apply_updates(variables["params"], updates)},
            parts)
        scalars = {k: v for k, v in parts.items()
                   if not isinstance(v, dict) and jnp.ndim(v) == 0}
        return variables, opt_state, {"loss": loss, **scalars}

    def epoch(params, opt_state, tokens):
        bare = "params" not in params
        variables = {"params": params} if bare else params

        def body(carry, toks):
            variables, opt_state, losses = step(*carry, toks)
            return (variables, opt_state), losses

        (variables, opt_state), losses = jax.lax.scan(
            body, (variables, opt_state), tokens)
        if bare:
            return variables["params"], opt_state, losses["loss"]
        return variables, opt_state, losses

    tok_sh = NamedSharding(mesh, P(None, "data"))
    jitted = jax.jit(
        epoch,
        in_shardings=(None, None, tok_sh),
        donate_argnums=(0, 1) if donate else (),
    )
    # the watched proxy passes attribute reads through to the jitted one
    jitted.loss_and_grads = loss_and_grads
    return core_telemetry.watch_compiles(jitted,
                                         name="training.lm_train_epoch")


def record_lm_stats(model, losses) -> None:
    """Count what an epoch of `make_lm_train_epoch` handed back into the
    counters the model names (`model.train_counters`: (part, counter)
    pairs).  `losses`: the epoch's third output AFTER the caller fetched
    it (numpy: this reads no device)."""
    for part, counter in model.train_counters:
        if part in losses:
            core_telemetry.incr(counter, int(np.sum(losses[part])))


def lm_params_to_3d(params, num_layers: int, pipe: int):
    """TransformerLM params -> the STACKED 3D-trainer layout:
    ``{"embed": {tok_embed[, pos_embed]}, "blocks": <stacked>, "out":
    {ln_f, head}}`` where every block leaf carries leading
    [P_stages, K_blocks] dims (stage p owns blocks p*K .. p*K+K-1, the
    contiguous split a pipe-sharded leading dim lays out for free).
    Shard with ``shard_params(p3, plan.mesh, lm_3d_rules())``."""
    if num_layers % pipe != 0:
        raise ValueError(f"num_layers={num_layers} not divisible by "
                         f"pipe={pipe}")
    k = num_layers // pipe
    blocks = [params[f"block{i}"] for i in range(num_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    stacked = jax.tree.map(
        lambda a: a.reshape((pipe, k) + a.shape[1:]), stacked)
    embed = {n: params[n] for n in ("tok_embed", "pos_embed")
             if n in params}
    return {"embed": embed, "blocks": stacked,
            "out": {"ln_f": params["ln_f"], "head": params["head"]}}


def lm_params_from_3d(params3d, num_layers: int):
    """Inverse of :func:`lm_params_to_3d` (back to the flax ``block{i}``
    layout model.apply consumes — eval/generation/export)."""
    flat = jax.tree.map(
        lambda a: a.reshape((num_layers,) + a.shape[2:]),
        params3d["blocks"])
    params = {f"block{i}": jax.tree.map(lambda a, i=i: a[i], flat)
              for i in range(num_layers)}
    params.update(params3d["embed"])
    params.update(params3d["out"])
    return params


def make_lm_train_step_3d(model, optimizer, plan, remat: bool = True,
                          donate: bool = True,
                          hang_budget_s: Optional[float] = None):
    """``step(params3d, opt_state, tokens) -> (params3d, opt_state,
    metrics)`` on a :class:`~mmlspark_tpu.parallel.mesh.MeshPlan`'s 3D
    mesh: data-parallel microbatches x megatron tensor rules x the GPipe
    schedule (`parallel.pipeline.gpipe_spmd_apply`), in ONE jitted
    program whose collectives XLA places from shardings — all but one,
    which the step's form decides: where the gradients meet over 'data'.

    ``tokens [A, M, mb, S]`` int32: A gradient-accumulation chunks of M
    pipeline microbatches of mb sequences (mb sharded over 'data', so
    divisible by it) — global batch A*M*mb.  Accumulation is an outer
    `lax.scan` summing grads across chunks before ONE optimizer update,
    so the HBM freed by sharding + remat converts directly into batch
    size.  The accumulation is LOCAL to a data replica: mb splits into
    ``[data, mb / data]``, the gradient is taken per replica (a `vmap`
    whose mapped dim is the 'data' axis), and the accumulators carry
    that leading ``[data]`` dim sharded over 'data' — one model shard's
    worth of f32 a device, as replicated accumulators would be.  The
    replicas' sums meet in ONE all-reduce after the scan, on the f32
    accumulators.  (`value_and_grad` of replicated parameters inside
    the scan would hand back replicated gradients, and GSPMD resolves
    that partial sum where it arises: every layer of every chunk.
    `parallel.mesh.collectives_by_loop` reads which of the two a
    compiled step is.)  ``remat``
    wraps each transformer block in `jax.checkpoint` with the
    dots-saveable policy: matmul outputs are kept, everything else
    (gelu, layernorm, attention softmax) recomputes in the backward —
    the classic activation-memory / recompute trade.  Params/opt_state
    are donated (the carry buffers die into their successors).

    ``params3d`` is the :func:`lm_params_to_3d` layout, sharded via
    ``shard_params(p3, plan.mesh, lm_3d_rules())``.  Loss is mean
    next-token cross-entropy (equal-size microbatches, so the mean of
    per-microbatch means equals the global mean and numerics match the
    single-device reference).  MoE aux losses are NOT folded in on this
    path yet.  Metrics carry loss + grad_norm — the TrainingGuard's
    probe pair.

    ``hang_budget_s`` bounds each step's collective entry with
    `parallel.distributed.run_with_deadline` (blocking until ready
    inside the budget): on a multi-host mesh a dead peer wedges the
    allreduce, and the budget turns that into a
    :class:`~mmlspark_tpu.parallel.distributed.CollectiveTimeout`
    instead of a silent stall — pair it with
    ``TrainingGuard.hang_budget_s()`` so the p95-derived watchdog model
    and the hard deadline agree."""
    import flax.linen as nn

    from ..parallel.pipeline import gpipe_spmd_apply
    from .transformer import _Block, default_attn

    mesh = plan.mesh
    if model.num_layers % plan.pipe != 0:
        raise ValueError(f"num_layers={model.num_layers} not divisible "
                         f"by pipe={plan.pipe}")
    attn = (model.attn_fn if model.attn_fn is not None
            else default_attn(True))
    blk = _Block(model.num_heads, model.mlp_ratio, model.dtype, attn,
                 dense_cls=model._dense_cls,
                 num_experts=model.moe_experts,
                 moe_capacity=model.moe_capacity,
                 rope=model.pos_emb == "rope",
                 kv_heads=model.num_kv_heads)
    tok_embed = nn.Embed(model.vocab_size, model.embed_dim,
                         dtype=model.dtype)
    pos_embed = (nn.Embed(model.max_len, model.embed_dim,
                          dtype=model.dtype)
                 if model.pos_emb == "learned" else None)
    ln_f = nn.LayerNorm(dtype=model.dtype)
    head = model._dense_cls(model.vocab_size, use_bias=False,
                            dtype=model.dtype)

    def block_apply(pblk, h):
        return blk.apply({"params": pblk}, h)

    if remat:
        block_apply = jax.checkpoint(
            block_apply, policy=jax.checkpoint_policies.dots_saveable)

    def stage_fn(pstage, h):
        # pstage leaves [K, ...]: this stage's K consecutive blocks
        h, _ = jax.lax.scan(
            lambda c, pb: (block_apply(pb, c), None), h, pstage)
        return h

    def embed_one(p3, toks):
        x = tok_embed.apply({"params": p3["embed"]["tok_embed"]}, toks)
        if pos_embed is not None:
            pe = pos_embed.apply({"params": p3["embed"]["pos_embed"]},
                                 jnp.arange(toks.shape[-1]))
            x = x + pe[None]
        return x

    def loss_of(p3, toks):
        # ONE data replica's share: toks [M, mb / data, S] -> mean
        # next-token CE over its microbatches.  No batch axis for the
        # pipeline buffer: the vmap below already is the `data` axis
        xs = jax.vmap(lambda t: embed_one(p3, t))(toks)
        hs = gpipe_spmd_apply(stage_fn, p3["blocks"], xs, mesh=mesh,
                              axis="pipe", batch_axis=None)

        def mb_loss(h, t):
            h = ln_f.apply({"params": p3["out"]["ln_f"]}, h)
            logits = head.apply({"params": p3["out"]["head"]}, h)
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1].astype(jnp.float32), t[:, 1:]))

        return jnp.mean(jax.vmap(mb_loss)(hs, toks))

    n_data = mesh.shape["data"]
    # per replica: the gradients come back with a leading [data] dim that
    # lives on the `data` axis, so no sum over `data` exists to resolve
    grad_of = jax.vmap(jax.value_and_grad(loss_of), in_axes=(None, 0),
                       spmd_axis_name="data")

    def step(params3d, opt_state, tokens):
        a, m, mb, s = tokens.shape
        # [A, M, mb, S] -> [A, data, M, mb / data, S]: replica d takes the
        # d-th contiguous slice of every microbatch, which is the slice
        # the tokens' sharding already put on its devices
        toks = tokens.reshape(a, m, n_data, mb // n_data, s)
        toks = jax.lax.with_sharding_constraint(
            toks.transpose(0, 2, 1, 3, 4),
            NamedSharding(mesh, P(None, "data")))

        def acc(carry, chunk):
            gsum, lsum = carry
            loss, grads = grad_of(params3d, chunk)
            return (jax.tree.map(jnp.add, gsum, grads),
                    lsum + loss), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros((n_data,) + p.shape, p.dtype), params3d)
        (gsum, lsum), _ = jax.lax.scan(
            acc, (zeros, jnp.zeros((n_data,), jnp.float32)), toks)
        # the step's one all-reduce over `data`: the replicas' f32 sums
        n = jnp.float32(a * n_data)
        grads = jax.tree.map(lambda g: g.sum(0) / n, gsum)
        updates, new_opt = optimizer.update(grads, opt_state, params3d)
        new_params = optax.apply_updates(params3d, updates)
        return new_params, new_opt, {
            "loss": lsum.sum() / n, "grad_norm": optax.global_norm(grads)}

    tok_sh = NamedSharding(mesh, P(None, None, "data", None))
    jitted = core_telemetry.watch_compiles(jax.jit(
        step,
        in_shardings=(None, None, tok_sh),
        donate_argnums=(0, 1) if donate else (),
    ), name="training.lm_train_step_3d")
    if hang_budget_s is None:
        return jitted

    from ..parallel.distributed import run_with_deadline
    seq = itertools.count()

    def guarded_step(params3d, opt_state, tokens):
        # the guarded path blocks until ready, so its wall IS the step's
        # compute — record it on the goodput ledger (the resumable loop
        # records its own steps; it builds the UNguarded factory and
        # wraps the deadline itself, so nothing double-counts)
        t0 = time.perf_counter()
        out = run_with_deadline(
            lambda: jax.block_until_ready(
                jitted(params3d, opt_state, tokens)),
            hang_budget_s, name="lm_train_step_3d")
        core_telemetry.LEDGER.record_step(
            next(seq), compute_s=time.perf_counter() - t0)
        return out

    return guarded_step


def make_lm_resumable_step_3d(model, optimizer, plan,
                              microbatches: int, grad_accum: int = 1,
                              remat: bool = True):
    """Adapter threading the 3D step through :func:`fit_epochs_resumable`
    (TrainState in/out, ``(state, tokens [B, S], labels-ignored)``
    signature): the flat batch reshapes to the step's [A, M, mb, S]
    accumulation layout.  B must equal A*M*mb for some mb."""
    inner = make_lm_train_step_3d(model, optimizer, plan, remat=remat)

    def step(state: TrainState, tokens, _labels):
        b = tokens.shape[0]
        if b % (grad_accum * microbatches) != 0:
            raise ValueError(
                f"batch {b} not divisible by grad_accum*microbatches="
                f"{grad_accum * microbatches}")
        toks = tokens.reshape(grad_accum, microbatches,
                              b // (grad_accum * microbatches),
                              tokens.shape[-1])
        new_params, new_opt, m = inner(state.params, state.opt_state, toks)
        return (TrainState(new_params, state.batch_stats, new_opt,
                           state.step + 1), m)

    return step


def make_eval_step(model, mesh: Optional[Mesh] = None):
    mesh = mesh or default_mesh()

    def step(variables, images):
        logits, _ = model.apply(variables, images, train=False)
        return jnp.argmax(logits, -1)

    return core_telemetry.watch_compiles(
        jax.jit(step, in_shardings=(None, batch_sharding(mesh, 4))),
        name="training.eval_step")


def init_train_state(model, optimizer, input_shape, seed: int = 0) -> TrainState:
    def _init():
        variables = model.init(
            {"params": jax.random.PRNGKey(seed)},
            jnp.zeros((1, *input_shape), jnp.float32),
            train=False,
        )
        params = variables["params"]
        return params, variables.get("batch_stats", {}), optimizer.init(params)

    # one compiled program instead of hundreds of eager init ops (each
    # its own dispatch and, the first time, its own compile)
    params, batch_stats, opt_state = jax.jit(_init)()
    return TrainState(params, batch_stats, opt_state)


def fit_epochs(
    step_fn,
    state: TrainState,
    images: np.ndarray,
    labels: np.ndarray,
    batch_size: int,
    epochs: int = 1,
    mesh: Optional[Mesh] = None,
    seed: int = 0,
    log_fn: Optional[Callable[[int, Dict[str, float]], None]] = None,
    epoch_fn=None,
) -> Tuple[TrainState, Dict[str, float]]:
    """Simple epoch loop over a host-resident dataset.  `batch_size` must be
    divisible by the mesh's data-parallel degree (static shapes; the remainder
    of each epoch is dropped, standard for training loops).

    With `epoch_fn` (from make_train_epoch) each epoch's shuffled batches are
    stacked [S, B, ...] and run as one scanned dispatch; `step_fn` is then
    only kept for callers that still want per-step logging."""
    mesh = mesh or default_mesh()
    dp = mesh.shape["data"]
    if batch_size % dp != 0:
        raise ValueError(f"batch_size {batch_size} not divisible by data-parallel degree {dp}")
    n = len(images)
    if n < batch_size:
        raise ValueError(
            f"dataset has {n} rows < batch_size {batch_size}; lower batch_size"
        )
    from ..io.feed import DeviceFeed
    from ..io.pipeline import HostPipeline, PipelineStage, pipeline_workers

    rng = np.random.default_rng(seed)
    metrics: Dict[str, float] = {}
    img_sh = NamedSharding(mesh, P(None, "data"))
    # ONE feed engine for the whole fit: each slice/batch transfer is
    # prefetched `depth` ahead (packed into a single device_put on one
    # device) so the host never sits in device_put between dispatches
    feed = DeviceFeed(mesh=mesh)
    for _epoch in range(epochs):
        order = rng.permutation(n)
        if epoch_fn is not None:
            steps = n // batch_size
            idx = order[: steps * batch_size]
            # scan in bounded slices: device memory stays O(slice) even for
            # datasets far larger than HBM; at most two compiled shapes
            # (the full slice and one remainder) across the whole fit
            step_bytes = (batch_size * int(np.prod(images.shape[1:]))
                          * images.dtype.itemsize
                          + batch_size * labels.dtype.itemsize)
            k = scan_slice_steps(steps, step_bytes)

            def assemble(bounds, idx=idx):
                # per-slice shuffled gather on a pipeline worker: slice
                # t+1 assembles (and its transfer prefetches) while slice
                # t's scanned epoch computes — and the epoch no longer
                # materializes a full shuffled copy of the dataset up
                # front; host memory stays O(slice)
                s, e = bounds
                sel = idx[s * batch_size : e * batch_size]
                return (images[sel].reshape(e - s, batch_size,
                                            *images.shape[1:]),
                        labels[sel].reshape(e - s, batch_size))

            pipe = HostPipeline([PipelineStage(
                "assemble", assemble, workers=pipeline_workers(2))])
            bounds = [(s, min(s + k, steps)) for s in range(0, steps, k)]
            for dbi, dbl in feed.stream(pipe.run(bounds),
                                        shardings=(img_sh, img_sh)):
                t0 = time.perf_counter()
                # the training.step span is also a profiler annotation:
                # a capture shows it on the device trace's clock
                with core_telemetry.span("training.step") as _sp:
                    state, ms = epoch_fn(state, dbi, dbl)
                    # one scanned dispatch = len(dbi) optimizer steps;
                    # block on the metrics so the timing covers the
                    # device work, not just async dispatch
                    jax.block_until_ready(ms)
                    _sp.attrs["steps"] = int(dbi.shape[0])
                dt = time.perf_counter() - t0
                k_real = max(1, int(dbi.shape[0]))
                core_telemetry.histogram(
                    "models.training.step_latency").observe(dt / k_real)
                core_telemetry.gauge(
                    "models.training.examples_per_sec").set(
                        k_real * batch_size / dt if dt > 0 else 0.0)
            metrics = {k2: float(np.asarray(v)[-1]) for k2, v in ms.items()}
            if log_fn:
                log_fn(int(state.step), metrics)
            continue
        batches = ((images[order[start : start + batch_size]],
                    labels[order[start : start + batch_size]])
                   for start in range(0, n - batch_size + 1, batch_size))
        for dbi, dbl in feed.stream(
                batches, shardings=(batch_sharding(mesh, 4),
                                    batch_sharding(mesh, 1))):
            t0 = time.perf_counter()
            with core_telemetry.span("training.step"):
                state, m = step_fn(state, dbi, dbl)
                # the float() pulls block on the step's device work, so
                # the measured wall is the true per-step cost, not
                # dispatch
                metrics = {k: float(v) for k, v in m.items()}
            dt = time.perf_counter() - t0
            core_telemetry.histogram(
                "models.training.step_latency").observe(dt)
            core_telemetry.gauge("models.training.examples_per_sec").set(
                batch_size / dt if dt > 0 else 0.0)
            if log_fn:
                log_fn(int(state.step), metrics)
    return state, metrics


def _autosave(mgr, state: TrainState, g: int) -> bool:
    """Best-effort checkpoint write: a failed save must not kill a healthy
    run (the previous checkpoint still covers resume) — warn, count
    ``checkpoint.write_failed``, keep training.  An InjectedCrash
    (BaseException) still propagates: that simulates process death, not a
    write error."""
    t0 = time.perf_counter()
    try:
        if g in mgr.all_steps():
            # a rollback replay re-reached a previously saved step: the
            # replayed trajectory (new lr_scale, quarantine skips)
            # supersedes the old bytes
            mgr.delete(g)
        mgr.save(state, step=g, wait=True)
        core_telemetry.incr("training.autosave")
        return True
    except Exception as e:
        core_telemetry.incr("checkpoint.write_failed")
        warnings.warn(f"checkpoint write failed at step {g}: {e!r}",
                      RuntimeWarning, stacklevel=2)
        return False
    finally:
        # goodput ledger: checkpoint wall is lost training time (a no-op
        # for the pre-training floor checkpoint — the ledger only arms
        # at the first recorded step)
        core_telemetry.LEDGER.note_lost(
            "checkpoint", time.perf_counter() - t0)


def fit_epochs_resumable(
    step_fn,
    state: TrainState,
    images: np.ndarray,
    labels: np.ndarray,
    batch_size: int,
    checkpoint_dir,
    epochs: int = 1,
    checkpoint_every: int = 50,
    max_to_keep: int = 3,
    mesh: Optional[Mesh] = None,
    seed: int = 0,
    log_fn: Optional[Callable[[int, Dict[str, float]], None]] = None,
    guard=None,
    step_factory: Optional[Callable[[float], Callable]] = None,
    elastic=None,
) -> Tuple[TrainState, Dict[str, float]]:
    """fit_epochs that survives being killed: auto-checkpoints every
    `checkpoint_every` steps through CheckpointManager and, on the next
    call with the same `checkpoint_dir`, resumes from the latest
    *verified* checkpoint — reproducing the uninterrupted run EXACTLY.

    Exactness rests on two invariants:

    * the batch schedule is a pure function of (seed, epoch): each
      epoch's shuffle uses its OWN ``np.random.default_rng([seed,
      epoch])``, so a resume at any global step regenerates the same
      order without replaying earlier epochs' draws (fit_epochs threads
      one RNG through all epochs — resumable cannot);
    * orbax restore is bit-exact, so the restored TrainState continues
      the identical float trajectory (asserted on CPU in tests; see
      docs/robustness.md "kill-and-resume").

    The loop runs per-step (the scanned epoch_fn path would quantize
    checkpoints to epoch boundaries) and crosses `fault_point
    ("training.step")` each executed step so chaos tests can kill it
    mid-epoch.  Checkpoints are numbered by **schedule position** (the
    global batch index ``g``), which equals ``state.step`` until a guard
    quarantine skips a batch — resume always continues the schedule, not
    the optimizer count.

    With a :class:`~mmlspark_tpu.models.guard.TrainingGuard` passed as
    ``guard``, every step's (loss, grad_norm) probes feed the anomaly
    ladder (docs/robustness.md "Training reliability ladder"): anomalous
    batches are quarantined (skipped on replay, persisted to
    ``quarantine.json`` in `checkpoint_dir`), the loop rolls back to the
    newest checkpoint that passes integrity verification, and the run
    aborts with :class:`~mmlspark_tpu.models.guard.TrainingAborted` once
    the guard's rollback budget is spent.  ``step_factory(lr_scale)``
    (optional) rebuilds the jitted step after each rollback so the
    guard's LR backoff actually reaches the optimizer; without it the
    rollback still replays cleanly at the original LR.  The fault points
    ``training.loss_nan`` / ``training.grad_nan`` poison a step's batch /
    gradient probe deterministically for chaos tests
    (tools/train_soak.py).

    With an :class:`~mmlspark_tpu.parallel.distributed.ElasticContext`
    passed as ``elastic``, the loop runs in multi-host mode: every step
    it beats this host's heartbeat lease and polls for peer loss
    (lease expiry detected by the coordinator's monitor, epoch adoption
    on followers, or an injected ``training.host_lost`` fault), and the
    step itself executes under a hang budget
    (``elastic.hang_budget_s``, else the guard's p95-derived
    ``hang_budget_s()``) so a dead peer's wedged allreduce raises
    ``CollectiveTimeout`` instead of stalling.  A detected loss runs the
    quarantine → shrink → resume ladder: ``guard.host_lost`` ledgers the
    peer into quarantine.json, the state rolls back to the newest
    verified checkpoint (per-shard crc re-verification; the restored
    leaves are host arrays, so they re-shard onto ANY mesh), the
    membership epoch advances (``elastic.commit_loss``), and
    ``elastic.rebuild(view)`` may hand back ``(mesh, step_fn)`` built
    over the survivors — the shrunken data axis — after which the
    schedule replays from the checkpoint floor with batches re-sharded
    onto the new mesh (docs/robustness.md "Elastic multi-host").

    Telemetry: ``training.autosave`` per checkpoint written (best-effort:
    a failed write warns + counts ``checkpoint.write_failed`` instead of
    killing the run), ``training.resume`` when a run starts from a
    restored step, plus the guard's ``training.anomaly/quarantine/
    rollback/abort/hang`` ledger.  Every executed step also lands on the
    goodput plane (docs/observability.md): a `StepTimeline` record
    (compute + the feed-measured h2d segment) on
    ``core_telemetry.LEDGER``, lost-time attribution for checkpoint
    writes / guard rollbacks / the elastic host-loss ladder, and one
    cadence-gated ``core_telemetry.STORE.tick()`` sweep."""
    from ..io.feed import DeviceFeed
    from ..parallel.distributed import run_with_deadline
    from ..utils.faults import InjectedFault, fault_point
    # lazy: checkpoint.py imports TrainState from this module
    from .checkpoint import CheckpointManager
    from .guard import GuardAction, TrainingAborted

    if checkpoint_every < 1:
        raise ValueError(
            f"checkpoint_every must be >= 1, got {checkpoint_every}")
    mesh = mesh or default_mesh()
    dp = mesh.shape["data"]
    if batch_size % dp != 0:
        raise ValueError(f"batch_size {batch_size} not divisible by "
                         f"data-parallel degree {dp}")
    n = len(images)
    steps_per_epoch = n // batch_size
    if steps_per_epoch < 1:
        raise ValueError(
            f"dataset has {n} rows < batch_size {batch_size}; lower batch_size")
    if step_fn is None:
        if step_factory is None:
            raise ValueError("need step_fn or step_factory")
        step_fn = step_factory(guard.lr_scale if guard is not None else 1.0)

    mgr = CheckpointManager(checkpoint_dir, max_to_keep=max_to_keep)
    qpath = os.path.join(os.fspath(checkpoint_dir), "quarantine.json")
    own_guard = guard is not None and not guard.running
    if own_guard:
        guard.start()
    def _on_corrupt(step, path):
        # corrupt checkpoints walked past get moved aside on disk AND
        # recorded in the guard's persisted ledger — the walk-back and
        # the quarantine must never disagree about which steps are dead
        if guard is not None:
            guard.quarantine_checkpoint(step, path)
            guard.save_quarantine(qpath)

    try:
        if guard is not None:
            guard.load_quarantine(qpath)
        latest = mgr.latest_step()
        g = int(state.step)
        if latest is not None and latest > int(state.step):
            try:
                # self-healing resume: newest checkpoint that VERIFIES
                # (corrupt ones are walked past, counting
                # checkpoint.corrupt/fallback, quarantined on disk)
                state, g = mgr.restore_verified(
                    template=state, on_corrupt=_on_corrupt,
                    quarantine=True)
                core_telemetry.incr("training.resume")
            except FileNotFoundError:
                # every checkpoint corrupt: start fresh rather than die
                g = int(state.step)
        g0 = g
        total = epochs * steps_per_epoch
        if guard is not None and total > g and mgr.latest_step() is None:
            # floor checkpoint: the ladder's rollback target must exist
            # before the first anomaly can need it
            _autosave(mgr, state, g)
        feed = DeviceFeed(mesh=mesh)
        img_sh = batch_sharding(mesh, np.ndim(images))
        lbl_sh = batch_sharding(mesh, np.ndim(labels))
        metrics: Dict[str, float] = {}
        order = None
        order_epoch = -1
        while g < total:
            lost = elastic.poll() if elastic is not None else None
            if lost:
                # the elastic ladder: ledger the dead peers, roll back to
                # the checkpoint floor, advance the membership epoch,
                # rebuild the mesh over the survivors, replay
                t_loss0 = time.perf_counter()
                view = elastic.commit_loss(lost)
                if guard is not None:
                    for h in lost:
                        guard.host_lost(h, {"epoch": view.epoch,
                                            "schedule_step": int(g)})
                    guard.save_quarantine(qpath)
                with core_telemetry.span("training.elastic.shrink") as sp:
                    try:
                        state, g = mgr.restore_verified(
                            template=state, on_corrupt=_on_corrupt,
                            quarantine=True)
                    except FileNotFoundError as e:
                        core_telemetry.incr("training.abort")
                        raise TrainingAborted(
                            f"host loss {lost} at schedule step {g} "
                            f"found no verifiable checkpoint: {e}") from e
                    sp.attrs["lost"] = ",".join(lost)
                    sp.attrs["epoch"] = view.epoch
                    sp.attrs["restored_step"] = g
                rebuilt = elastic.rebuild(view)
                if rebuilt is not None:
                    mesh, step_fn = rebuilt
                    dp = mesh.shape["data"]
                    if batch_size % dp != 0:
                        raise ValueError(
                            f"batch_size {batch_size} not divisible by "
                            f"surviving data-parallel degree {dp} "
                            f"(epoch {view.epoch})")
                    feed = DeviceFeed(mesh=mesh)
                    img_sh = batch_sharding(mesh, np.ndim(images))
                    lbl_sh = batch_sharding(mesh, np.ndim(labels))
                core_telemetry.incr("training.resume")
                # the whole ladder — quarantine, restore, epoch commit,
                # mesh rebuild — is the host-loss window the goodput
                # plane attributes (detection -> resume)
                core_telemetry.LEDGER.note_lost(
                    "host_loss", time.perf_counter() - t_loss0)
                continue
            epoch, b = divmod(g, steps_per_epoch)
            if epoch != order_epoch:
                # schedule is (seed, epoch)-pure: resume regenerates it
                order = np.random.default_rng([seed, epoch]).permutation(n)
                order_epoch = epoch
            if guard is not None and g in guard.quarantined:
                # a batch the ladder already condemned: skip on replay
                # (the optimizer count no longer advances for it — that
                # is why checkpoints are numbered by g, not state.step)
                core_telemetry.incr("training.quarantine.skip")
                g += 1
                if g % checkpoint_every == 0:
                    _autosave(mgr, state, g)
                continue
            fault_point("training.step")
            poison_loss = poison_grad = False
            try:
                fault_point("training.loss_nan")
            except InjectedFault:
                poison_loss = True
            try:
                fault_point("training.grad_nan")
            except InjectedFault:
                poison_grad = True
            idx = order[b * batch_size:(b + 1) * batch_size]
            xb, yb = images[idx], labels[idx]
            if poison_loss and np.issubdtype(xb.dtype, np.floating):
                # a genuinely poisoned batch: NaN data → NaN loss → NaN
                # grads, end to end through the real jitted step
                xb = np.full_like(xb, np.nan)
            h2d0 = feed.telemetry.transfer_seconds()
            dbi, dbl = feed.put_group([xb, yb],
                                      shardings=(img_sh, lbl_sh))
            h2d_s = feed.telemetry.transfer_seconds() - h2d0
            def _exec(st=state, xi=dbi, yi=dbl):
                ns, m = step_fn(st, xi, yi)
                # float() forces the sync, so execution (collectives
                # included) lands inside the deadline below, not after
                return ns, {k: float(v) for k, v in m.items()}

            t0 = time.perf_counter()
            with core_telemetry.span("training.step"):
                if guard is not None:
                    guard.step_begin(g)
                try:
                    if elastic is not None:
                        # multi-host mode: a dead peer wedges the
                        # allreduce — bound every collective entry
                        budget = elastic.hang_budget_s
                        if budget is None and guard is not None:
                            budget = guard.hang_budget_s()
                        new_state, metrics = run_with_deadline(
                            _exec, budget, name="training.step")
                    else:
                        new_state, metrics = _exec()
                finally:
                    if guard is not None:
                        guard.step_end()
            dt = time.perf_counter() - t0
            core_telemetry.histogram(
                "models.training.step_latency").observe(dt)
            core_telemetry.gauge("models.training.examples_per_sec").set(
                batch_size / dt if dt > 0 else 0.0)
            # goodput plane: this step's timeline record (compute + the
            # h2d segment the feed telemetry measured) and one cadence-
            # gated timeseries sweep — a few dict writes on the hot path
            core_telemetry.LEDGER.record_step(int(g), compute_s=dt,
                                              h2d=h2d_s)
            core_telemetry.STORE.tick()
            action = GuardAction.OK
            if guard is not None:
                loss = metrics.get("loss", float("nan"))
                if poison_loss and math.isfinite(loss):
                    # integer-input models can't carry NaN through the
                    # batch; poison the probe itself instead
                    loss = float("nan")
                grad_norm = metrics.get("grad_norm")
                if poison_grad:
                    grad_norm = float("nan")
                action = guard.observe(g, loss, grad_norm)
            if action == GuardAction.ABORT:
                guard.save_quarantine(qpath)
                raise TrainingAborted(
                    f"guard exhausted its rollback budget "
                    f"({guard.max_rollbacks}) at schedule step {g}; "
                    f"quarantined={sorted(map(repr, guard.quarantined))}")
            if action == GuardAction.ROLLBACK:
                # persist the verdict BEFORE restoring: a crash here must
                # not forget which batch was poisoned
                t_rb0 = time.perf_counter()
                guard.save_quarantine(qpath)
                with core_telemetry.span("training.guard.rollback") as sp:
                    try:
                        # new_state (not the donated pre-step state) is
                        # the only guaranteed-alive template
                        state, g = mgr.restore_verified(
                            template=new_state, on_corrupt=_on_corrupt,
                            quarantine=True)
                    except FileNotFoundError as e:
                        core_telemetry.incr("training.abort")
                        raise TrainingAborted(
                            f"rollback at schedule step {g} found no "
                            f"verifiable checkpoint: {e}") from e
                    sp.attrs["restored_step"] = g
                    sp.attrs["lr_scale"] = guard.lr_scale
                if step_factory is not None:
                    step_fn = step_factory(guard.lr_scale)
                core_telemetry.LEDGER.note_lost(
                    "rollback", time.perf_counter() - t_rb0)
                continue
            state = new_state
            if log_fn:
                log_fn(int(state.step), metrics)
            g += 1
            if g % checkpoint_every == 0:
                _autosave(mgr, state, g)
        if total > g0 and g % checkpoint_every != 0:
            _autosave(mgr, state, g)  # final state always resumable
        if guard is not None and guard.quarantined:
            guard.save_quarantine(qpath)
    finally:
        if own_guard:
            guard.stop()
        mgr.close()
    return state, metrics


def make_distill_epoch(
    teacher,
    teacher_variables,
    student,
    optimizer,
    mesh: Optional[Mesh] = None,
    temperature: float = 2.0,
    alpha: float = 0.7,
    donate: bool = False,
):
    """`epoch(params, opt_state, tokens) -> (params, opt_state, losses)`:
    knowledge distillation for LMs, scanned like make_lm_train_epoch.

    Student loss = alpha * KL(teacher_T || student_T) * T^2
                 + (1-alpha) * next-token cross-entropy.
    The trained student is the natural DRAFT for speculative_generate:
    distillation maximizes exactly the agreement the acceptance rate
    measures.  Teacher forwards run under stop_gradient (no teacher
    grads, no teacher optimizer state)."""
    mesh = mesh or default_mesh()
    t2 = jnp.float32(temperature) ** 2

    def step(params, opt_state, toks):
        t_logits, _ = teacher.apply(teacher_variables, toks)
        t_logp = jax.nn.log_softmax(
            jax.lax.stop_gradient(t_logits[:, :-1].astype(jnp.float32))
            / temperature)

        def loss_fn(p):
            s_logits, _ = student.apply({"params": p}, toks)
            s32 = s_logits[:, :-1].astype(jnp.float32)
            s_logp_t = jax.nn.log_softmax(s32 / temperature)
            kl = jnp.mean(jnp.sum(
                jnp.exp(t_logp) * (t_logp - s_logp_t), axis=-1)) * t2
            lp = jax.nn.log_softmax(s32)
            ll = jnp.take_along_axis(lp, toks[:, 1:][..., None], axis=-1)
            ce = -jnp.mean(ll)
            return alpha * kl + (1.0 - alpha) * ce

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def epoch(params, opt_state, tokens):
        def body(carry, toks):
            params, opt_state = carry
            params, opt_state, loss = step(params, opt_state, toks)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), tokens)
        return params, opt_state, losses

    tok_sh = NamedSharding(mesh, P(None, "data"))
    return core_telemetry.watch_compiles(jax.jit(
        epoch,
        in_shardings=(None, None, tok_sh),
        donate_argnums=(0, 1) if donate else (),
    ), name="training.distill_epoch")
