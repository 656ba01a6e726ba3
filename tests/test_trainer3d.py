"""3D-mesh GSPMD trainer (models/training.py): stacked param layout
round-trip, loss parity of the composed (data x tensor x pipe) step
against the single-device reference, remat's measured memory saving, the
gpipe GSPMD schedule, and sharded-checkpoint per-shard verification with
quarantine walk-back (ISSUE 17).

Everything runs on the conftest-forced 8-device virtual CPU mesh.
"""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.models.training import (TrainState, lm_params_from_3d,
                                          lm_params_to_3d,
                                          make_lm_train_step_3d,
                                          shard_params)
from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.parallel.mesh import MeshPlan
from mmlspark_tpu.parallel.sharding_rules import lm_3d_rules

V, E, L, H, S = 256, 32, 4, 4, 16


def _model(dtype=jnp.float32):
    return transformer_lm(vocab_size=V, embed_dim=E, num_layers=L,
                          num_heads=H, max_len=S, dtype=dtype)


def _init(model):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16, S), 0, V,
                              jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks[0, :2])["params"]
    return params, toks


def test_lm_params_3d_roundtrip_is_exact():
    model = _model()
    params, _ = _init(model)
    p3 = lm_params_to_3d(params, L, pipe=2)
    stacked = jax.tree.leaves(p3["blocks"])
    assert all(a.shape[:2] == (2, L // 2) for a in stacked)
    back = lm_params_from_3d(p3, L)
    jax.tree.map(np.testing.assert_array_equal, back, params)


def test_lm_params_to_3d_rejects_indivisible_layers():
    model = _model()
    params, _ = _init(model)
    with pytest.raises(ValueError, match="divisible"):
        lm_params_to_3d(params, L, pipe=3)


def _reference_trajectory(model, params, opt, toks, steps=2):
    """`steps` plain one-device steps over toks[i] [B, S]: losses, gradient
    norms and the parameters they end at."""
    def ref_step(p, o, t):
        def loss_fn(p):
            logits, _ = model.apply({"params": p}, t)
            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1].astype(jnp.float32), t[:, 1:]))

        loss, grads = jax.value_and_grad(loss_fn)(p)
        up, o = opt.update(grads, o, p)
        return optax.apply_updates(p, up), o, loss, optax.global_norm(grads)

    p_ref, o_ref = params, opt.init(params)
    losses, norms = [], []
    for i in range(steps):
        p_ref, o_ref, l, n = ref_step(p_ref, o_ref, toks[i])
        losses.append(float(l))
        norms.append(float(n))
    return losses, norms, p_ref


def _plan(d, t, p):
    return MeshPlan(data=d, model=t, pipe=p,
                    devices=jax.devices()[:d * t * p])


def _assert_3d_matches_reference(model, params, toks, dims, accum, micro,
                                 atol=1e-4):
    opt = optax.sgd(0.1)
    ref_losses, ref_norms, p_ref = _reference_trajectory(
        model, params, opt, toks)
    plan = _plan(*dims)
    p3 = shard_params(lm_params_to_3d(params, L, plan.pipe), plan.mesh,
                      lm_3d_rules())
    o3 = opt.init(p3)
    step = make_lm_train_step_3d(model, opt, plan, remat=True,
                                 donate=False)
    for i in range(2):
        tb = toks[i].reshape(accum, micro, -1, S)  # [A, M, mb, S]
        p3, o3, m = step(p3, o3, tb)
        assert abs(float(m["loss"]) - ref_losses[i]) < atol
        assert abs(float(m["grad_norm"]) - ref_norms[i]) < atol
    # trained params match the reference trajectory, not just the loss
    back = lm_params_from_3d(jax.device_get(p3), L)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(
            jax.device_get(p_ref))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=atol)


def test_3d_step_matches_single_device_reference():
    """(2,2,2): all three parallelisms at once, 2 steps — the second
    step consumes the first's updated params so a wrong gradient
    anywhere compounds instead of cancelling."""
    model = _model()
    params, toks = _init(model)
    _assert_3d_matches_reference(model, params, toks, (2, 2, 2),
                                 accum=2, micro=2)


@pytest.mark.parametrize("dims,accum,micro", [
    ((2, 2, 1), 4, 1),    # the benchmark cell's mesh: mb 4, 2 a replica
    ((2, 1, 2), 4, 2),    # data x pipe: one sequence a replica a chunk
    ((2, 2, 2), 8, 1),
    ((1, 2, 2), 4, 2),    # data=1: the trivial case of the same code
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_3d_step_accumulates_per_data_replica(dims, accum, micro):
    """A >= 4 chunks: each data replica sums its own chunks' gradients
    and the replicas' sums meet once, after the scan.  The sum must be
    the same sum: loss, grad_norm and two SGD steps' parameters against
    the one-device reference (SGD, not Adam: Adam's first step is
    lr * sign(g), which a reordered sum flips where g is near zero)."""
    model = _model()
    params, toks = _init(model)
    _assert_3d_matches_reference(model, params, toks, dims, accum, micro)


def test_3d_step_trains_moe_blocks():
    """Switch-MoE blocks through the same `loss_of` under the per-replica
    gradient, expert weights sharded over `model`.  Capacity is set so
    that no token is dropped: which tokens share a routing group then
    does not matter and the one-device trajectory is the reference."""
    model = transformer_lm(vocab_size=V, embed_dim=E, num_layers=L,
                           num_heads=H, max_len=S, dtype=jnp.float32,
                           moe_experts=4, moe_capacity=4.0)
    params, toks = _init(model)
    assert "moe" in params["block0"]
    _assert_3d_matches_reference(model, params, toks, (2, 2, 1),
                                 accum=4, micro=1)


def test_3d_step_rejects_microbatch_not_divisible_by_data():
    model = _model()
    params, toks = _init(model)
    opt = optax.sgd(0.1)
    plan = _plan(2, 2, 1)
    p3 = shard_params(lm_params_to_3d(params, L, 1), plan.mesh,
                      lm_3d_rules())
    step = make_lm_train_step_3d(model, opt, plan, donate=False)
    with pytest.raises(ValueError, match="divisible"):
        step(p3, opt.init(p3), toks[0][:6].reshape(2, 1, 3, S))


def _data_collectives(model, params, toks, plan, accum, micro):
    """(records in loops, bytes outside loops) of the compiled step's
    collectives whose replica groups span the `data` axis."""
    from mmlspark_tpu.parallel.mesh import collectives_by_loop

    opt = optax.sgd(0.1)
    p3 = shard_params(lm_params_to_3d(params, L, plan.pipe), plan.mesh,
                      lm_3d_rules())
    step = make_lm_train_step_3d(model, opt, plan, donate=False)
    tb = toks.reshape(-1, S)[:accum * micro * 4].reshape(accum, micro, 4, S)
    compiled = step.lower(p3, opt.init(p3), tb).compile()
    recs = [r for r in collectives_by_loop(compiled, plan.mesh)
            if "data" in r["axes"]]
    return ([r for r in recs if r["loops"] > 0],
            sum(r["bytes"] for r in recs if r["loops"] == 0))


@pytest.mark.parametrize("dims,accum,micro", [
    ((2, 2, 1), 4, 1), ((2, 2, 2), 2, 2), ((2, 1, 2), 2, 2),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_3d_step_all_reduces_gradients_once_outside_the_loops(
        dims, accum, micro):
    """The compiled program, not a proxy: no all-reduce over `data`
    larger than an activation inside a `while` body (the parent sent one
    layer's weight gradients there, K x A times a step), and the bytes
    the step sends over `data` do not grow with the chunk count."""
    model = _model()
    params, toks = _init(model)
    plan = _plan(*dims)
    activation = 4 // plan.data * S * E * 4
    in_loops, once = _data_collectives(model, params, toks, plan,
                                       accum, micro)
    heavy = [r for r in in_loops
             if r["kind"] == "all-reduce" and r["bytes"] > activation]
    assert not heavy, heavy
    # every gradient is all-reduced, so at least a model shard's worth
    n_params = sum(a.size for a in jax.tree.leaves(params))
    assert once >= n_params * 4 // (plan.model * plan.pipe)
    in_loops2, once2 = _data_collectives(model, params, toks, plan,
                                         2 * accum, micro)
    assert once2 == once
    assert (sum(r["bytes"] for r in in_loops2)
            == sum(r["bytes"] for r in in_loops))


def test_collectives_by_loop_reads_both_group_spellings():
    """The helper on a hand-written module: loop depth through nested
    `while` bodies (a fusion or a call adds none), iota and literal
    replica groups, a tuple result, an async permute's result element."""
    from mmlspark_tpu.parallel.mesh import collectives_by_loop

    hlo = """HloModule m
%add (a: f32[], b: f32[]) -> f32[] {
  ROOT %s = f32[] add(%a, %b)
}
%inner_body (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %g = bf16[8,4]{1,0:T(8,128)(2,1)} all-reduce(%x), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add
  %cp = (f32[2,4]{1,0}, f32[2,4]{1,0}, u32[], u32[]) collective-permute-start(%y), channel_id=2, source_target_pairs={{0,1},{2,3}}
}
%inner_cond (p: (s32[], f32[8,4])) -> pred[] {
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}
%outer_body (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %w = (s32[], f32[8,4]) while(%t), condition=%inner_cond, body=%inner_body
  %a2a = (f32[4]{0}, f32[4]{0}) all-to-all(%u, %v), channel_id=3, replica_groups=[2,2]<=[4]
}
%called (p: f32[8]) -> f32[8] {
  ROOT %ag = f32[8]{0} all-gather(%p), channel_id=4, replica_groups={{0,1,2,3}}, dimensions={0}
}
ENTRY %main (p0: f32[8,4]) -> f32[8,4] {
  %w2 = (s32[], f32[8,4]) while(%t0), condition=%inner_cond, body=%outer_body
  %c = f32[8] call(%q), to_apply=%called
  ROOT %last = f32[8,4]{1,0} all-reduce(%z), channel_id=5, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
}
"""
    mesh = _plan(2, 2, 1).mesh
    got = {r["name"]: r for r in collectives_by_loop(hlo, mesh)}
    assert [got[n]["loops"] for n in ("g", "cp", "a2a", "ag", "last")] \
        == [2, 2, 1, 0, 0]
    assert got["g"]["groups"] == [[0, 2], [1, 3]]
    assert got["g"]["axes"] == ("data",) and got["g"]["bytes"] == 64
    assert got["cp"]["kind"] == "collective-permute"
    assert got["cp"]["bytes"] == 32 and got["cp"]["axes"] == ("model",)
    assert got["a2a"]["groups"] == [[0, 1], [2, 3]]
    assert got["a2a"]["bytes"] == 32 and got["a2a"]["axes"] == ("model",)
    assert got["ag"]["axes"] == ("data", "model")
    assert got["last"]["axes"] == ("data",) and got["last"]["bytes"] == 128
    assert collectives_by_loop(hlo)[0]["axes"] is None


def test_remat_reduces_compiled_temp_memory():
    """jax.checkpoint(dots_saveable) on the blocks must show up in XLA's
    own memory analysis — the acceptance criterion is the compiler's
    number, not a proxy."""
    model = _model(jnp.bfloat16)
    params, toks = _init(model)
    opt = optax.sgd(0.1)
    plan = MeshPlan(data=2, model=2, pipe=2)
    p3 = shard_params(lm_params_to_3d(params, L, 2), plan.mesh,
                      lm_3d_rules())
    o3 = opt.init(p3)
    tb = toks[0].reshape(2, 2, 4, S)
    temp = {}
    for remat in (False, True):
        step = make_lm_train_step_3d(model, opt, plan, remat=remat,
                                     donate=False)
        ma = step.lower(p3, o3, tb).compile().memory_analysis()
        temp[remat] = int(ma.temp_size_in_bytes)
    assert temp[True] < temp[False], temp


def test_gpipe_spmd_apply_matches_sequential():
    from mmlspark_tpu.parallel.pipeline import (gpipe_spmd_apply,
                                                stack_stage_params)

    rng = np.random.default_rng(0)
    p, m, mb, d = 4, 6, 2, 8

    def stage(params, x):
        return jnp.tanh(x @ params["w"]) + params["b"]

    per_stage = [{"w": jnp.asarray(rng.normal(size=(d, d)) * 0.3,
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(d,)) * 0.1,
                                   jnp.float32)}
                 for _ in range(p)]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rng.normal(size=(m, mb, d)), jnp.float32)
    plan = MeshPlan(data=2, model=1, pipe=4)
    got = gpipe_spmd_apply(stage, stacked, x, mesh=plan.mesh)
    want = x
    for sp in per_stage:
        want = jax.vmap(lambda b, _p=sp: stage(_p, b))(want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # mismatched stage count must raise, not silently skip stages
    with pytest.raises(ValueError, match="stage"):
        gpipe_spmd_apply(stage, stacked, x, mesh=MeshPlan(
            data=4, model=1, pipe=2).mesh)


# ------------------------------- sharded checkpoints: per-shard crc32

def _counter(name):
    return telemetry.counters().get(name, 0)


def _sharded_state():
    model = _model()
    params, toks = _init(model)
    opt = optax.sgd(0.1)
    plan = MeshPlan(data=2, model=2, pipe=2)
    p3 = shard_params(lm_params_to_3d(params, L, 2), plan.mesh,
                      lm_3d_rules())
    return TrainState(p3, {}, opt.init(p3), step=0), plan


def test_manifest_records_per_shard_crc32_for_sharded_leaves(tmp_path):
    from mmlspark_tpu.models.checkpoint import (MANIFEST_NAME,
                                                CheckpointManager)

    state, _ = _sharded_state()
    mgr = CheckpointManager(str(tmp_path))
    try:
        mgr.save(state, step=1)
        with open(tmp_path / "1" / MANIFEST_NAME) as f:
            doc = json.load(f)
        assert doc["format"] == 2
        sharded = {k: v for k, v in doc["leaves"].items()
                   if "shards" in v}
        assert sharded, "no per-shard entries for a sharded save"
        entry = sharded["['params']['blocks']['qkv']['kernel']"]
        assert "pipe" in entry["spec"] and "model" in entry["spec"]
        # pipe x tensor sharding: 4 distinct shards, disjoint bounds
        assert len(entry["shards"]) == 4
        assert len({tuple(map(tuple, s["index"]))
                    for s in entry["shards"]}) == 4
        # replicated leaves carry no shard table
        assert "shards" not in doc["leaves"][
            "['params']['embed']['tok_embed']['embedding']"]
    finally:
        mgr.close()


def test_tampered_shard_crc_names_the_failing_shard(tmp_path):
    """Direct unit of the per-shard verify: corrupt ONE shard's recorded
    crc and the error must name the (leaf, spec, shard)."""
    from mmlspark_tpu.models.checkpoint import (MANIFEST_NAME,
                                                CheckpointCorruptError,
                                                CheckpointManager)

    state, _ = _sharded_state()
    mgr = CheckpointManager(str(tmp_path))
    try:
        mgr.save(state, step=1)
        mpath = tmp_path / "1" / MANIFEST_NAME
        with open(mpath) as f:
            doc = json.load(f)
        key = "['params']['blocks']['proj']['kernel']"
        doc["leaves"][key]["shards"][2]["crc32"] ^= 0xDEAD
        with open(mpath, "w") as f:
            json.dump(doc, f)
        with pytest.raises(CheckpointCorruptError, match="shard=2"):
            mgr.restore(step=1, template=state)
    finally:
        mgr.close()


@pytest.mark.chaos
def test_flipped_shard_byte_rejects_quarantines_and_resumes_prior(
        tmp_path):
    """The ISSUE-17 satellite end to end: flip one byte inside one shard
    of a multi-shard save -> restore_verified rejects the step, the
    TrainingGuard records the quarantined directory, and resume lands on
    the previous verified step."""
    from mmlspark_tpu.models.checkpoint import CheckpointManager
    from mmlspark_tpu.models.guard import TrainingGuard

    state, _ = _sharded_state()
    mgr = CheckpointManager(str(tmp_path))
    guard = TrainingGuard(watchdog=False)
    qpath = tmp_path / "quarantine.json"
    try:
        mgr.save(state, step=1)
        state2 = TrainState(
            jax.tree.map(lambda a: a + 1e-3, state.params),
            {}, state.opt_state, step=1)
        mgr.save(state2, step=2)

        # one byte, one shard: the orbax data blobs under step 2
        victims = sorted(glob.glob(str(tmp_path / "2" / "**" / "d" / "*"),
                                   recursive=True))
        assert victims, "orbax layout changed: no data files under d/"
        with open(victims[0], "r+b") as f:
            f.seek(os.path.getsize(victims[0]) // 2)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))

        def on_corrupt(step, path):
            guard.quarantine_checkpoint(step, path)
            guard.save_quarantine(qpath)

        c0 = _counter("checkpoint.quarantine")
        restored, step = mgr.restore_verified(
            template=state, on_corrupt=on_corrupt, quarantine=True)
        # resume lands on the previous verified step...
        assert step == 1 and int(restored.step) == 0
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)),
            restored.params, jax.device_get(state.params))
        # ...the poisoned directory moved aside, evidence intact...
        assert not (tmp_path / "2").exists()
        assert (tmp_path / "quarantined" / "2").exists()
        assert _counter("checkpoint.quarantine") > c0
        # ...and the guard's persisted ledger names it
        assert guard.quarantined_checkpoints
        with open(qpath) as f:
            doc = json.load(f)
        assert [2, str(tmp_path / "quarantined" / "2")] in \
            doc["quarantined_checkpoints"]
        # a fresh guard loads the ledger back (crash-restart path)
        g2 = TrainingGuard(watchdog=False)
        g2.load_quarantine(qpath)
        assert g2.quarantined_checkpoints == guard.quarantined_checkpoints
    finally:
        mgr.close()
