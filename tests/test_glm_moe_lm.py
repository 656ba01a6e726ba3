"""GlmMoeLM (models/glm_moe_lm.py) against the plain reference
(benchmarks/lib/reference_glm.py) at a small size on the CPU, in float32:
logits, both losses, every gradient leaf; the share, the selection bias
and its controller, the MTP module's reach, and the trainer's path."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lib import reference_glm as ref
from mmlspark_tpu.models.glm_moe_lm import BIAS, GlmMoeLM
from mmlspark_tpu.models.moe_lm import _SparseMLP
from mmlspark_tpu.models.training import make_lm_train_epoch, record_lm_stats

SIZES = dict(vocab_size=96, embed_dim=64, num_layers=3, num_heads=4,
             qk_nope_dim=16, qk_rope_dim=8, v_head_dim=24, q_lora_rank=32,
             kv_lora_rank=24, dense_width=128, expert_width=32,
             shared_width=32, num_experts=8, experts_held=(0, 4), top_k=2,
             dtype=jnp.float32, loss_chunk=16)
ARCH = dict(layers=3, dense=1, heads=4, nope=16, rope=8, v=24, kv_rank=24,
            theta=1e6, eps=1e-5, experts=8, top_k=2, scaling=1.8, mtp=1)


def _one():
    """A mesh of one device: these batches are smaller than the test
    session's eight virtual ones."""
    from mmlspark_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=jax.devices()[:1])


def _variables(model, tokens, seed=0, bias_scale=0.0):
    v = model.init(jax.random.PRNGKey(seed), tokens)
    v = {k: v[k] for k in ("params", BIAS)}
    if bias_scale:
        keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
        v[BIAS] = jax.tree.map(
            lambda b: bias_scale * jax.random.normal(next(keys), b.shape),
            v[BIAS])
    return v


@pytest.fixture(scope="module")
def setup():
    model = GlmMoeLM(**SIZES)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 24), 0, 96)
    return model, tokens, _variables(model, tokens, bias_scale=0.05)


def test_logits_and_both_losses_match_the_reference(setup):
    model, tokens, v = setup
    logits, taps = model.apply(v, tokens)
    loss, parts = model.lm_objective(v, tokens)
    want_loss, want = ref.loss(v["params"], v[BIAS], tokens, ARCH, 0.3)
    for b in range(2):
        out = ref.sequence(v["params"], v[BIAS], tokens[b], ARCH,
                           mtp_logits=True)
        np.testing.assert_allclose(logits[b], out["logits"], atol=2e-4)
        # the program's last MTP position merges a token that is not there
        np.testing.assert_allclose(taps["mtp_logits"][b, :-1],
                                   out["mtp_logits"], atol=2e-4)
    np.testing.assert_allclose(parts["ce_main"], want["ce_main"], rtol=1e-5)
    np.testing.assert_allclose(parts["ce_mtp"], want["ce_mtp"], rtol=1e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert float(parts["ce_mtp"]) > 1.0


def test_every_gradient_leaf_matches_the_reference(setup):
    model, tokens, v = setup
    epoch = make_lm_train_epoch(model, optax.adam(1e-3), mesh=_one(), donate=False)
    (_loss, _parts), got = jax.jit(epoch.loss_and_grads)(v, tokens)
    want = jax.grad(lambda p: ref.loss(p, v[BIAS], tokens, ARCH, 0.3)[0])(
        v["params"])
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert flat_got.keys() == flat_want.keys()
    for path, g in flat_got.items():
        w = flat_want[path]
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * scale + 1e-6, (
            jax.tree_util.keystr(path))
        assert float(jnp.max(jnp.abs(w))) > 0, jax.tree_util.keystr(path)


def test_counts_and_controller_follow_the_reference(setup):
    model, tokens, v = setup
    _loss, parts = model.lm_objective(v, tokens)
    _l, want = ref.loss(v["params"], v[BIAS], tokens, ARCH, 0.3)
    loads = ref.loads(want["routing"], 8)
    names = ["layer1", "layer2"]
    got = [parts["load"][n]["moe"]["counts"] for n in names] + [
        parts["load"]["mtp"]["block"]["moe"]["counts"]]
    for g, w in zip(got, loads):
        np.testing.assert_array_equal(g, w)
    # the MTP block's last position is nobody's: S - 1 rows a sequence
    assert int(got[-1].sum()) == 2 * 23 * 2 and int(got[0].sum()) == 2 * 24 * 2
    moved = model.lm_controller(v, parts)
    for name, load in zip(names, loads):
        np.testing.assert_allclose(
            moved[BIAS][name]["moe"]["bias"],
            ref.bias_after(v[BIAS][name]["moe"]["bias"], load, 1e-3),
            rtol=0, atol=1e-9)
    step = moved[BIAS]["layer1"]["moe"]["bias"] - v[BIAS]["layer1"]["moe"]["bias"]
    size = np.abs(np.asarray(step, np.float64))
    assert np.all(np.isclose(size, 0.0, atol=1e-7)
                  | np.isclose(size, 1e-3, atol=1e-7))
    assert moved["params"] is v["params"]


def _layer(held, **kw):
    return _SparseMLP(num_experts=16, top_k=3, width=32, shared_width=32,
                      scaling=1.8, held=held, dtype=jnp.float32,
                      renormalise=True, choice_bias=True, scores="sigmoid",
                      bias_collection=BIAS, **kw)


def test_eight_shares_add_up_to_the_uncut_sigmoid_layer():
    y = jax.random.normal(jax.random.PRNGKey(0), (40, 64))
    whole = _layer((0, 16))
    v = whole.init(jax.random.PRNGKey(1), y)
    v = {"params": v["params"],
         BIAS: {"bias": 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))}}
    full = whole.apply(v, y)
    shared = whole.apply(
        {**v, "params": {**v["params"], **{
            k: jnp.zeros_like(v["params"][k]) for k in ("w1", "w3", "w2")}}},
        y)
    total = shared                      # what every chip computes alike, once
    for i in range(8):
        lo, hi = 2 * i, 2 * i + 2
        part = {**v["params"], **{k: v["params"][k][lo:hi]
                                  for k in ("w1", "w3", "w2")}}
        total = total + _layer((lo, hi)).apply({**v, "params": part}, y) - shared
    np.testing.assert_allclose(total, full, atol=2e-5)


def test_bias_changes_the_choice_and_never_a_weight():
    y = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
    layer = _layer((0, 16))
    v = layer.init(jax.random.PRNGKey(1), y)
    params = v["params"]

    def run(bias):
        out, mut = layer.apply({"params": params, BIAS: {"bias": bias}}, y,
                               mutable=["routing"])
        return out, mut["routing"]["experts"][0]

    zero = jnp.zeros(16)
    out0, e0 = run(zero)
    # a bias on every expert alike changes neither choice nor output ...
    out1, e1 = run(zero + 0.3)
    np.testing.assert_array_equal(e0, e1)
    np.testing.assert_array_equal(out0, out1)
    # ... one on a single expert makes it chosen everywhere, weighted by
    # its own unbiased score
    out2, e2 = run(zero.at[5].set(10.0))
    assert bool(jnp.all(jnp.any(e2 == 5, -1))) and not bool(
        jnp.all(jnp.any(e0 == 5, -1)))
    r = y @ params["router"]
    s = jax.nn.sigmoid(r)
    top = jnp.take_along_axis(s, e2, -1)
    w = 1.8 * top / jnp.sum(top, -1, keepdims=True)
    routed = out2 - layer.apply(
        {"params": {**params, **{k: jnp.zeros_like(params[k])
                                 for k in ("w1", "w3", "w2")}},
         BIAS: {"bias": zero}}, y)
    want = sum(
        w[:, j, None] * ((jax.nn.silu(y @ params["w1"][e]) * (y @ params["w3"][e]))
                         @ params["w2"][e]) * (e2[:, j, None] == e)
        for j in range(3) for e in range(16))
    np.testing.assert_allclose(routed, want, atol=2e-5)
    # ... and no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(layer.apply(
        {"params": params, BIAS: {"bias": b}}, y) ** 2))(zero + 0.01)
    np.testing.assert_array_equal(g, jnp.zeros(16))


def test_mtp_logits_reach_one_token_ahead_and_no_further(setup):
    model, tokens, v = setup
    i = 9
    base = model.apply(v, tokens)[1]["mtp_logits"]
    later = tokens.at[:, i + 2:].set((tokens[:, i + 2:] + 1) % 96)
    moved = model.apply(v, later)[1]["mtp_logits"]
    np.testing.assert_array_equal(base[:, :i + 1], moved[:, :i + 1])
    assert float(jnp.max(jnp.abs(base[:, i + 1] - moved[:, i + 1]))) > 1e-3
    nxt = tokens.at[:, i + 1].set((tokens[:, i + 1] + 1) % 96)
    moved = model.apply(v, nxt)[1]["mtp_logits"]
    np.testing.assert_array_equal(base[:, :i], moved[:, :i])
    assert float(jnp.max(jnp.abs(base[:, i] - moved[:, i]))) > 1e-3
    # the main head at position i sees nothing after t_i
    main = model.apply(v, nxt)[0]
    np.testing.assert_array_equal(model.apply(v, tokens)[0][:, :i + 1],
                                  main[:, :i + 1])


def test_the_epoch_trains_and_hands_back_parts_and_statistics(setup):
    model, tokens, v = setup
    opt = optax.adam(3e-3)
    epoch = make_lm_train_epoch(model, opt, mesh=_one(), donate=False)
    stack = jnp.stack([tokens, tokens, tokens, tokens])
    v2, _state, out = epoch(v, opt.init(v["params"]), stack)
    out = {k: np.asarray(a) for k, a in out.items()}
    assert out["loss"].shape == (4,) and out["loss"][-1] < out["loss"][0]
    np.testing.assert_allclose(out["loss"],
                               out["ce_main"] + 0.3 * out["ce_mtp"], rtol=1e-6)
    assert (out["attn_pairs"] == 2 * (3 * 24 * 25 // 2 + 23 * 24 // 2)).all()
    assert (out["mtp_tokens"] == 2 * 22).all()
    assert (out["moe_load_max_all"] >= out["moe_load_max"]).all()
    assert (out["moe_assignments"] <= 3 * 2 * 24 * 2).all()
    # four steps of +-gamma a step
    bias = v2[BIAS]["layer1"]["moe"]["bias"] - v[BIAS]["layer1"]["moe"]["bias"]
    assert float(jnp.max(jnp.abs(bias))) <= 4e-3 + 1e-7
    assert float(jnp.max(jnp.abs(bias))) > 0
    from mmlspark_tpu.core import telemetry

    before = telemetry.counters("training.")
    record_lm_stats(model, out)
    after = telemetry.counters("training.")
    assert (after["training.attn.pairs"]
            - before.get("training.attn.pairs", 0)) == int(out["attn_pairs"].sum())
    assert (after["training.moe.assignments"]
            - before.get("training.moe.assignments", 0)) == int(
        out["moe_assignments"].sum())


def test_dense_models_train_as_they_did():
    """TransformerLM through make_lm_train_epoch: the losses of the step
    written out as it was before models could bring an objective, bit for
    bit."""
    from mmlspark_tpu.models.transformer import transformer_lm

    model = transformer_lm(vocab_size=64, embed_dim=32, num_layers=2,
                           num_heads=2, max_len=16, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (3, 2, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(0), tokens[0])["params"]
    opt = optax.adam(1e-2)
    epoch = make_lm_train_epoch(model, opt, mesh=_one(), donate=False)
    _p, _s, losses = epoch(params, opt.init(params), tokens)

    def loss_fn(p, toks):
        (logits, _), _mut = model.apply({"params": p}, toks,
                                        mutable=["losses"])
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), toks[:, 1:]))

    @jax.jit
    def plain(params, state, tokens):
        def body(carry, toks):
            p, s = carry
            loss, g = jax.value_and_grad(loss_fn)(p, toks)
            up, s = opt.update(g, s, p)
            return (optax.apply_updates(p, up), s), loss
        return jax.lax.scan(body, (params, state), tokens)[1]

    np.testing.assert_array_equal(np.asarray(losses),
                                  np.asarray(plain(params, opt.init(params),
                                                   tokens)))
    (loss, _parts), grads = jax.jit(epoch.loss_and_grads)(
        {"params": params}, tokens[0])
    np.testing.assert_allclose(loss, losses[0], rtol=1e-6)
