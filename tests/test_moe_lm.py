"""MoELM (models/moe_lm.py) against the plain float32 reference of the
Laguna family (benchmarks/lib/reference_laguna.py), at a small size on
the CPU with seeded random weights: the forward, prefill then decode
through both kinds of page pool, the rotary embeddings and the window
mask each alone, the shares of the routed experts, and the kernels in
interpret mode against their XLA paths."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.models import moe_lm
from mmlspark_tpu.models.moe_lm import MoELM
from mmlspark_tpu.ops import attention_kernels as ak
from mmlspark_tpu.ops import grouped_matmul as gm
from mmlspark_tpu.ops import paged_attention as pa
from mmlspark_tpu.serving.batcher import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROPE_FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
             "original_max_position_embeddings": 8192, "beta_slow": 1,
             "beta_fast": 32, "attention_factor": 1.4852030263919618,
             "partial_rotary_factor": 0.5}
ROPE_WINDOW = {"rope_type": "default", "rope_theta": 10000,
               "partial_rotary_factor": 1}
# the configuration file's `rehearse` preset, spelled as a config.json
CFG = {
    "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 1,
    "num_attention_heads_per_layer": [2, 3, 3, 3, 2],
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4, "sliding_window": 8,
    "intermediate_size": 128, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "num_hidden_layers": 5, "vocab_size": 128,
    "published": {"num_experts": 8},
    "rope_parameters": {"full_attention": ROPE_FULL,
                        "sliding_attention": ROPE_WINDOW},
}


@pytest.fixture(scope="module")
def ref():
    """benchmarks/lib/reference_laguna.py by path."""
    spec = importlib.util.spec_from_file_location(
        "reference_laguna", os.path.join(ROOT, "benchmarks", "lib",
                                         "reference_laguna.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _model(held=8, max_len=64, dtype=jnp.float32):
    return MoELM.from_config(dict(CFG, num_experts=held), max_len, dtype)


@pytest.fixture(scope="module")
def params():
    model = _model()
    return model.init(jax.random.PRNGKey(7),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _share(params, lo, hi):
    """The parameter tree of the chip that holds experts [lo, hi)."""
    def cut(path, a):
        names = [p.key for p in path]
        if "moe" in names and names[-1] in ("w1", "w2", "w3") \
                and "shared" not in names:
            return a[lo:hi]
        return a
    return jax.tree_util.tree_map_with_path(cut, params)


def test_forward_logits_match_the_reference(ref, params):
    model = _model()
    tokens = np.random.default_rng(0).integers(0, 128, (2, 40))
    got, _taps = model.apply({"params": params}, jnp.asarray(tokens))
    arch = ref.arch_of(CFG)
    for row in range(2):
        want = ref.logits(params, jnp.asarray(tokens[row]), arch)
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)


def test_half_held_forward_matches_the_reference_share(ref, params):
    """experts_held routes over all eight and sums the four held."""
    model = _model(held=4)
    share = _share(params, 0, 4)
    tokens = np.random.default_rng(1).integers(0, 128, (1, 24))
    got, _taps = model.apply({"params": share}, jnp.asarray(tokens))
    want = ref.logits(share, jnp.asarray(tokens[0]),
                      ref.arch_of(dict(CFG, num_experts=4)))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_yarn_half_rotary_matches_the_reference(ref):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, 50, 3, 16)), jnp.float32)
    pos = jnp.arange(50) + 0
    inv = moe_lm.yarn_inv_freq(8, 500000.0, 128.0, 8192, 32.0, 1.0)
    got = moe_lm._rotate(x, pos, inv, ROPE_FULL["attention_factor"])
    arch = {"rope_full": ROPE_FULL, "rope_window": ROPE_WINDOW}
    want = ref.rope(x[0], False, arch)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-5)
    # the second half of the head passes unrotated, the first does not
    assert np.array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(got[0, 5:, :, :8]),
                           np.asarray(x[0, 5:, :, :8]))
    # window layers: the shared plain rope over the whole head
    from mmlspark_tpu.models.transformer import _rope

    np.testing.assert_allclose(np.asarray(_rope(x, pos, 10000.0)[0]),
                               np.asarray(ref.rope(x[0], True, arch)),
                               atol=1e-5)


def test_yarn_blend_at_the_published_sizes(ref):
    """64 rotated dimensions: plain below the beta_fast correction
    dimension, 1/128 of plain above the beta_slow one."""
    inv = np.asarray(moe_lm.yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0,
                                          1.0))
    plain = 1.0 / 500000.0 ** (np.arange(32) * 2 / 64)
    np.testing.assert_allclose(inv[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(inv[-8:], plain[-8:] / 128, rtol=1e-6)
    np.testing.assert_allclose(
        inv, np.asarray(ref._yarn_inv_freq(ROPE_FULL, 128)), rtol=1e-6)


@pytest.mark.parametrize("window", [None, 8, 24])
def test_window_mask_matches_the_reference(ref, window):
    """Softmax over exactly the keys the reference's mask shows."""
    rng = np.random.default_rng(3)
    s = 40
    q = jnp.asarray(rng.normal(size=(1, s, 3, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, 1, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, 1, 16)), jnp.float32)
    got = ak.prefill_attention(q, k, v, window, kernel=False)[0]
    seen = np.asarray(ref.mask(s, window))
    sc = np.einsum("qhd,kd->hqk", np.asarray(q[0]), np.asarray(k[0, :, 0]))
    sc = np.where(seen[None], sc / 4.0, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hqk,kd->qhd", p, np.asarray(v[0, :, 0]))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    if window is not None:
        assert seen.sum(1).max() == window


def test_shares_add_up_to_the_uncut_layer(ref, params):
    """Two chips, four experts each: their routed parts, with the shared
    expert they both compute counted once, are the uncut reference
    layer."""
    p = params["layer2"]["moe"]
    y = jnp.asarray(np.random.default_rng(4).normal(size=(30, 64)),
                    jnp.float32)
    arch = ref.arch_of(CFG)
    with jax.default_matmul_precision("highest"):
        uncut, _top, _deficit = ref._sparse(p, y, arch, 0)
        shared = ref._gated(y, p["shared"]["w1"], p["shared"]["w3"],
                            p["shared"]["w2"])
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        layer = moe_lm._SparseMLP(num_experts=8, top_k=2, width=32,
                                  shared_width=32, scaling=2.5,
                                  held=(lo, hi), dtype=jnp.float32)
        mine = dict(p, w1=p["w1"][lo:hi], w2=p["w2"][lo:hi],
                    w3=p["w3"][lo:hi])
        parts.append(layer.apply({"params": mine}, y) - shared)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(uncut), atol=2e-4)
    assert float(jnp.abs(parts[0]).max()) > 0.01
    assert float(jnp.abs(parts[1]).max()) > 0.01


def test_reference_with_chosen_experts(ref, params):
    """Given the program's own choices the reference computes the same
    network (deficit 0 everywhere).  Given a worse expert it says by how
    many router-logit units; it computes with that set only inside the
    band it is given, and with ITS OWN set outside it."""
    arch = ref.arch_of(CFG)
    tokens = jnp.asarray(np.random.default_rng(11).integers(0, 128, 20))
    h, own, deficits = ref.hidden(params, tokens, arch)
    h2, own2, d2 = ref.hidden(params, tokens, arch, chosen=jnp.stack(own))
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h), atol=1e-6)
    assert not np.asarray(jnp.stack(deficits)).any()
    assert not np.asarray(jnp.stack(d2)).any()
    worse = jnp.stack(own).at[0, :, 1].set(
        (jnp.stack(own)[0, :, 0] + 3) % 8)      # some other expert
    h3, own3, d3 = ref.hidden(params, tokens, arch, chosen=worse)
    assert np.array_equal(np.asarray(jnp.stack(own3)[0]),
                          np.asarray(own[0]))   # its own choice stands
    gaps = np.asarray(jnp.stack(d3)[0])
    assert gaps.max() > 0
    # band 0 (the default): the reference's own routing judges
    np.testing.assert_allclose(np.asarray(h3), np.asarray(h), atol=1e-6)
    # a band over every gap: the given sets are taken, the output moves
    h4, _o, _d = ref.hidden(params, tokens, arch, chosen=worse,
                            band=float(gaps.max()) + 1.0)
    assert float(jnp.abs(h4 - h).max()) > 1e-3
    # a band between the gaps, one layer by itself (rows are independent
    # there): only the near ties are taken
    moe = params["layer1"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(2), (20, 64))
    with jax.default_matmul_precision("highest"):
        out, own_e, _z = ref._sparse(moe, y, arch, 0)
        given = own_e.at[:, 1].set((own_e[:, 0] + 3) % 8)
        _o, _e, gap = ref._sparse(moe, y, arch, 0, given, 0.0)
        mid = float(np.median(np.asarray(gap)))
        out2, _e, _g = ref._sparse(moe, y, arch, 0, given, mid)
    moved = np.asarray(jnp.abs(out2 - out).max(-1)) > 1e-6
    assert np.array_equal(moved, (np.asarray(gap) <= mid)
                          & (np.asarray(gap) > 0))
    assert moved.any() and not moved.all()


def _taps_of(model, params, tokens):
    """What the model's routed layers read and wrote over tokens [S]."""
    from mmlspark_tpu.serving.batcher import _by_tap

    _out, kept = model.apply({"params": params}, tokens[None],
                             mutable=["routing"])
    return {tap: v[:, 0] for tap, v in _by_tap(kept["routing"]).items()}


def test_layer_check_passes_the_model_and_sees_each_fault(ref, params):
    """The equations on a routed layer's own input: the model's router,
    top-k and routed sum agree to float rounding; a rounded router, a
    top-k off by one rank and a perturbed routed sum each show in their
    own reading."""
    arch = ref.arch_of(CFG)
    tokens = jnp.asarray(np.random.default_rng(13).integers(0, 128, 24))
    taps = _taps_of(_model(), params, tokens)
    assert set(taps) == {"input", "router_input", "logits", "experts",
                         "routed"}
    assert taps["input"].shape == (4, 24, 64)       # four routed layers
    moe = params["layer2"]["moe"]
    one = {tap: v[1] for tap, v in taps.items()}
    ok = jax.tree.map(np.asarray, ref.layer_check(moe, one, arch))
    assert ok["router_err"].max() < 1e-5
    assert ok["route_miss"].max() < 1e-6 and not ok["differs"].any()
    assert np.sqrt(ok["routed_sq"].sum() / ok["routed_ref_sq"].sum()) < 1e-5
    rounded = dict(one, logits=one["logits"].astype(jnp.bfloat16))
    bad = ref.layer_check(moe, rounded, arch)
    assert float(bad["router_err"].max()) > 1e-3
    # the k-th expert swapped for the (k+1)-th of the equations' own order
    order = jnp.argsort(-one["logits"], -1)
    off = jnp.concatenate([order[:, :1], order[:, 2:3]], -1)
    bad = ref.layer_check(moe, dict(one, experts=off), arch)
    gap = np.asarray(jnp.take_along_axis(one["logits"], order[:, 1:2], -1)
                     - jnp.take_along_axis(one["logits"], order[:, 2:3], -1))
    np.testing.assert_allclose(np.asarray(bad["route_miss"]), gap[:, 0],
                               atol=1e-5)
    assert np.asarray(bad["differs"]).all()
    bad = ref.layer_check(moe, dict(one, routed=one["routed"] * 1.01), arch)
    err = np.sqrt(float(bad["routed_sq"].sum() / bad["routed_ref_sq"].sum()))
    assert 0.009 < err < 0.011


# ---- serving: both kinds of page pool ------------------------------------
@pytest.fixture(scope="module")
def served(params):
    """Seven requests over three slots (so slots are reused), page 4,
    window 8 (a ring of three pages), contexts past the window and past
    page boundaries."""
    telemetry.reset_counters("serving.")
    model = _model()
    batcher = ContinuousBatcher(model, {"params": params}, max_slots=3,
                                paged=True, page_size=4)
    held = []
    grow = batcher._win.grow

    def watched(slot, pos):
        out = grow(slot, pos)
        held.append(max(len(p) for p in batcher._win.slot_pages))
        return out

    batcher._win.grow = watched
    batcher.start()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 128, n).tolist()
               for n in (5, 13, 21, 3, 30, 9, 17)]
    wants = (20, 7, 12, 30, 10, 25, 5)
    try:
        streams = [batcher.submit(p, max_new_tokens=k)
                   for p, k in zip(prompts, wants)]
        replies = [s.tokens() for s in streams]
    finally:
        batcher.stop()
    return batcher, prompts, replies, wants, held


def test_prefill_then_decode_matches_the_reference_forward(ref, params,
                                                           served):
    _b, prompts, replies, wants, _held = served
    arch = ref.arch_of(CFG)
    fwd = jax.jit(lambda t: ref.logits(params, t, arch))
    for prompt, reply, want in zip(prompts, replies, wants):
        assert len(reply) == want
        lg = np.asarray(fwd(jnp.asarray(prompt + reply)))
        for j, tok in enumerate(reply):
            row = lg[len(prompt) + j - 1]
            assert row.max() - row[tok] < 2e-3, (len(prompt), j)


def test_window_pages_are_recycled_and_bounded(served):
    batcher, _p, _r, _w, held = served
    win = batcher._win
    assert win.ring == 8 // 4 + 1 == 3
    assert max(held) == win.ring          # never more than window/page + 1
    counted = telemetry.counters("serving.batcher.pages.")
    assert counted["serving.batcher.pages.window_recycled"] > 0
    assert 0 < counted["serving.batcher.pages.window"] < \
        counted["serving.batcher.pages.full"]


def test_admission_tiles_are_counted_where_the_kernel_runs(served):
    """The model names a head shape a cache kind (two query heads a KV
    head on full, three on window layers, 16 wide); off the TPU the
    admission runs the XLA composition and the batcher holds no shape
    to count tiles at."""
    batcher, _prompts, *_ = served
    assert batcher.model.attn_shapes == ((2, 16, 16), (3, 16, 16))
    assert batcher._attn_shapes == ()


def test_a_bucket_cut_at_max_len_is_admitted_and_counts_no_tiles(
        monkeypatch):
    """`max_len` 1,000, which no block of the admission kernel tiles:
    a prompt of 600 is admitted at a bucket of 1,000, a shape the
    kernel declines (the XLA composition runs, as before the kernel
    took lengths) and the count reads as no tiles, not as an error on
    the loop thread; a prompt of 100 at the 128 bucket counts its one
    tile a kind both ways.  The count is on (`on_single_tpu` forced
    for the batcher alone; the programs are the CPU's)."""
    from mmlspark_tpu.serving import batcher as batcher_mod

    monkeypatch.setattr(batcher_mod, "on_single_tpu", lambda: True)
    model = MoELM.from_config(dict(CFG, head_dim=128), 1000, jnp.float32)
    variables = model.init(jax.random.PRNGKey(3),
                           jnp.zeros((1, 8), jnp.int32))
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8)
    assert batcher._attn_shapes == ((2, 128, 128), (3, 128, 128))
    assert batcher._bucket(600) == 1000
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 128, n).tolist() for n in (600, 100)]
    names = ("serving.batcher.prefill.attn_tiles",
             "serving.batcher.prefill.attn_tiles_bucket")
    before = telemetry.counters("serving.batcher.prefill.attn_tiles")
    batcher.start()
    try:
        replies = [batcher.submit(p, max_new_tokens=2).tokens()
                   for p in prompts]
    finally:
        batcher.stop()
    after = telemetry.counters("serving.batcher.prefill.attn_tiles")
    assert [after.get(n, 0) - before.get(n, 0) for n in names] == [2, 2]
    for prompt, reply in zip(prompts, replies):
        logits, _taps = model.apply(variables, jnp.asarray([prompt]))
        row = np.asarray(logits[0, -1])
        assert row.max() - row[reply[0]] < 2e-3


def test_free_lists_return_to_full(served):
    batcher = served[0]
    win = batcher._win
    assert sorted(batcher._free) == list(range(1, batcher._np))
    assert batcher._avail == batcher._np - 1
    assert sorted(win.free) == list(range(1, win.np))
    assert win.avail == win.np - 1
    assert not batcher._table.any() and not win.table.any()


def test_routing_counters_ride_the_token_fetch(served):
    counted = telemetry.counters("serving.moe.")
    assert counted["serving.moe.assignments"] > 0
    assert counted["serving.moe.experts_touched"] > 0
    # idle slots and bucket padding are computed, and are nobody's tokens
    assert 0 < counted["serving.moe.live_assignments"] < \
        counted["serving.moe.assignments"]
    # a layer's busiest expert cannot draw more than its live assignments
    assert counted["serving.moe.load_max"] <= \
        counted["serving.moe.live_assignments"]


def test_live_rows_are_the_prompts_own(params):
    """A prompt's padding and a pad row draw experts like any row, and
    count in the work, not in the load."""
    model = _model()
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 128, (2, 16)))
    counted = {}
    for last in ([15, 15], [4, -1]):
        _out, kept = model.apply({"params": params}, toks,
                                 jnp.asarray(last), method=model.prefill,
                                 mutable=["stats"])
        from mmlspark_tpu.serving.batcher import _sum_stats
        counted[tuple(last)] = np.asarray(_sum_stats(
            kept["stats"], moe_lm.STAT_NAMES))
    whole, part = counted[(15, 15)], counted[(4, -1)]
    assert whole[0] == part[0] == 2 * 16 * 2 * 4      # rows x top-k x layers
    assert whole[2] == whole[0] and part[2] == 5 * 2 * 4
    assert part[3] <= part[2]


@pytest.mark.parametrize("lengths", [(5, 11), (16, 1), (9, 0)])
def test_first_tokens_do_not_see_the_buckets_padding(params, lengths):
    """An admission's logits at each prompt's last token, and the K/V
    rows of its own positions, are the same whatever bucket the prompt
    is padded into and whatever the padding holds: attention runs over
    a row's first `lengths` positions (a pad row has none)."""
    model = _model()
    rng = np.random.default_rng(5)
    last = jnp.asarray(lengths, jnp.int32) - 1
    narrow = rng.integers(0, 128, (2, 16))
    wide = rng.integers(0, 128, (2, 32))          # other padding, and more
    for row, n in enumerate(lengths):
        wide[row, :n] = narrow[row, :n]
    got = [model.apply({"params": params}, jnp.asarray(toks), last,
                       method=model.prefill, mutable=["stats"])[0]
           for toks in (narrow, wide)]
    for row, n in enumerate(lengths):
        if n == 0:
            continue                               # a pad row: no token
        np.testing.assert_allclose(np.asarray(got[0][0][row]),
                                   np.asarray(got[1][0][row]), atol=1e-5)
        for kv_a, kv_b in zip(got[0][1], got[1][1]):
            for a, b in zip(kv_a, kv_b):
                np.testing.assert_allclose(np.asarray(a[row, :n]),
                                           np.asarray(b[row, :n]),
                                           atol=1e-5)


def test_teacher_force_replays_what_was_served(ref, params, served):
    """The served requests replayed through the same batcher hand back
    the logits that chose every served token, and per routed layer the
    layer's input, router logits, chosen experts and routed sum at every
    position fed; the free lists come back whole."""
    batcher, prompts, replies, _wants, _held = served
    arch = ref.arch_of(CFG)
    pairs = list(zip(prompts, replies))[:3]
    with pytest.raises(ValueError, match="slots"):
        batcher.teacher_force(list(zip(prompts, replies)))
    got = batcher.teacher_force(pairs)
    fwd = jax.jit(lambda t: ref.logits(params, t, arch))
    model = _model()
    for (prompt, reply), g in zip(pairs, got):
        n, m = len(prompt), len(reply)
        assert g["logits"].shape == (m, 128)
        assert g["logits"].argmax(-1).tolist() == reply
        lg = np.asarray(fwd(jnp.asarray(prompt + reply)))[n - 1:n + m - 1]
        np.testing.assert_allclose(g["logits"], lg, atol=2e-3)
        want = _taps_of(model, params, jnp.asarray(prompt + reply))
        for tap, v in g["routing"].items():
            assert v.shape[:2] == (4, n + m - 1), tap
            if tap == "experts":
                same = np.sort(v, -1) == np.sort(
                    np.asarray(want[tap])[:, :n + m - 1], -1)
                assert same.mean() > 0.98       # near ties may fall over
            else:
                np.testing.assert_allclose(
                    v, np.asarray(want[tap])[:, :n + m - 1], atol=5e-3)
    test_free_lists_return_to_full(served)
    assert batcher._step is not None and all(r is None
                                             for r in batcher._live)


# ---- the decode loop one step ahead, over both kinds of pool ----------------
@pytest.fixture(scope="module")
def mixed(ref, params):
    """test_serving_model.MIXED through a model's own programs: page 4 and a
    window of 8, so the ring of three pages is shorter than most replies
    and turns over with a step in flight."""
    from test_serving_model import mixed_requests, run_mixed

    from mmlspark_tpu.serving import batcher as batcher_mod

    model = _model()
    arch = ref.arch_of(CFG)
    fwd = jax.jit(lambda t: ref.logits(params, t, arch))
    margins = []

    def reference(prompt, n):
        # greedy over the plain forward, one token at a time at a fixed
        # width (causal: the padding is unseen)
        seq = list(prompt)
        for _ in range(n):
            toks = np.zeros(64, np.int32)
            toks[:len(seq)] = seq
            row = np.asarray(fwd(jnp.asarray(toks)))[len(seq) - 1]
            second, best = np.sort(row)[-2:]
            margins.append(float(best - second))
            seq.append(int(row.argmax()))
        return seq[len(prompt):]

    requests = mixed_requests(np.random.default_rng(3), 128, 64, reference)
    names = (batcher_mod.TICK_OVERLAPPED, batcher_mod.TICK_LATE_DISCARDS)
    before = {n: telemetry.counters().get(n, 0) for n in names}
    batcher = ContinuousBatcher(model, {"params": params}, max_slots=3,
                                paged=True, page_size=4)
    try:
        replies, steps = run_mixed(batcher, requests)
    finally:
        batcher.stop()
    counted = {n: telemetry.counters().get(n, 0) - before[n] for n in names}
    return batcher, requests, replies, steps, counted, min(margins)


def test_mixed_replies_are_the_references(mixed):
    """Token for token and in length, where the reference's own choice
    is not a near tie (the served programs and the plain forward round
    differently in the last digits)."""
    _b, requests, replies, _steps, _counted, margin = mixed
    assert margin > 1e-4              # no tie to fall the other way here
    assert [len(r) for r in replies] == [len(w) for *_x, w in requests]
    assert replies == [w for *_x, w in requests]
    prompt, _m, _eos, want = requests[0]
    assert len(prompt) + len(want) == 64          # it ends at max_len


def test_mixed_run_counts_and_gives_everything_back(mixed):
    from test_serving_model import late_ends

    from mmlspark_tpu.serving import batcher as batcher_mod

    batcher, requests, _replies, steps, counted, _margin = mixed
    assert steps == len(requests[0][3]) - 1
    assert counted[batcher_mod.TICK_OVERLAPPED] == steps - 1
    assert counted[batcher_mod.TICK_LATE_DISCARDS] == late_ends(requests) == 2
    assert not batcher._flight and not batcher._inflight.any()
    test_free_lists_return_to_full((batcher,))
    assert batcher._win.slot_reserved == batcher._slot_reserved == [0] * 3


def test_stop_with_a_step_in_flight_leaves_a_batcher_to_replay_in(params):
    """The loop is stopped between two iterations, a step dispatched and
    not fetched; its two requests keep their slots.  `teacher_force` in
    the two other slots hands back the logits a never-started batcher's
    replay does."""
    model = _model()
    rng = np.random.default_rng(12)
    pairs = [(rng.integers(0, 128, n).tolist(),
              rng.integers(0, 128, m).tolist())
             for n, m in ((6, 15), (11, 9))]

    def make():
        return ContinuousBatcher(model, {"params": params}, max_slots=4,
                                 paged=True, page_size=4,
                                 idle_sleep_s=0.0005)

    fresh = make().teacher_force(pairs)
    batcher = make().start()
    try:
        streams = [batcher.submit(rng.integers(0, 128, n).tolist(),
                                  max_new_tokens=40) for n in (5, 14)]
        for stream in streams:
            it = iter(stream)
            for _ in range(14):       # past the ring's first turn
                next(it)
    finally:
        batcher.stop()
    assert not batcher._thread.is_alive()
    assert not batcher._flight and not batcher._inflight.any()
    assert [r is not None for r in batcher._live] == [True, True, False, False]
    held = (len(batcher._free), batcher._avail, len(batcher._win.free),
            batcher._win.avail)
    replayed = batcher.teacher_force(pairs)
    for got, want in zip(replayed, fresh):
        np.testing.assert_allclose(got["logits"], want["logits"], atol=1e-5)
        for tap, v in got["routing"].items():
            if tap != "experts":
                np.testing.assert_allclose(v, want["routing"][tap], atol=1e-5)
    assert held == (len(batcher._free), batcher._avail,
                    len(batcher._win.free), batcher._win.avail)


def test_unsupported_modes_are_refused(params):
    model = _model()
    with pytest.raises(ValueError, match="paged=True"):
        ContinuousBatcher(model, {"params": params}, paged=False)
    batcher = ContinuousBatcher(model, {"params": params}, paged=True,
                                page_size=4)
    with pytest.raises(ValueError, match="BLOCK decode"):
        batcher.register_prefix([1, 2, 3, 4, 5])


def test_rows_cap_splits_a_bucket(params):
    batcher = ContinuousBatcher(_model(), {"params": params}, paged=True,
                                page_size=4)
    assert [batcher._rows_cap(b) for b in (16, 32, 64)] == [4, 2, 1]


# ---- kernels in interpret mode against their XLA paths --------------------
def _draws(kind, rng, rows, top_k, n_exp):
    if kind == "all_on_one":        # expert 3 draws everything, the rest none
        return np.full((rows, top_k), 3)
    if kind == "none_held":         # nothing falls on the share
        return np.zeros((rows, top_k), np.int64)
    return np.stack([rng.permutation(n_exp)[:top_k] for _ in range(rows)])


@pytest.mark.parametrize("kind,rows,top_k,held,dtype", [
    ("random", 300, 2, (0, 8), jnp.float32),
    ("random", 300, 3, (2, 6), jnp.float32),
    ("all_on_one", 300, 1, (0, 8), jnp.float32),
    ("none_held", 200, 2, (6, 8), jnp.float32),
    ("random", 300, 2, (0, 8), jnp.bfloat16),
])
def test_grouped_matmul_backward_matches_ragged_dot_autodiff(kind, rows, top_k,
                                                             held, dtype):
    """`expert_mlp(train=True)`: the kernel arm's hand-written backward
    (interpret mode) against `jax.grad` through `ragged_dot`, for the
    rows, the three weights and the combine weights; an expert that drew
    no row gets a zero gradient, one that drew every row all of it."""
    rng = np.random.default_rng(8)
    e, f, n_exp = 128, 256, 8
    lo, hi = held
    x = jnp.asarray(rng.normal(size=(rows, e)), dtype)
    w1, w3 = (jnp.asarray(rng.normal(size=(hi - lo, e, f)) * e ** -0.5, dtype)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(hi - lo, f, e)) * f ** -0.5, dtype)
    ids = jnp.asarray(_draws(kind, rng, rows, top_k, n_exp), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(rows, top_k)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(rows, e)), jnp.float32)

    def loss(x, w1, w3, w2, weights, kernel):
        plan = gm.dispatch(ids, lo, hi, 128)
        y = gm.expert_mlp(x, plan, w1, w3, w2, 128, kernel=kernel, train=True)
        return jnp.sum(gm.combine(y, plan, weights) * probe)

    grads = [jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                     static_argnums=5)(x, w1, w3, w2, weights, kernel)
             for kernel in (True, False)]
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for got, want, name in zip(*grads, ("x", "w1", "w3", "w2", "weights")):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all(), name
        assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0), name
    dw1 = np.asarray(grads[0][1], np.float32)
    if kind == "all_on_one":
        assert np.abs(dw1[3]).max() > 0
        assert not np.delete(dw1, 3, 0).any()
    if kind == "none_held":
        assert not dw1.any() and not np.asarray(grads[0][0], np.float32).any()


@pytest.mark.parametrize("rows,top_k,held", [(32, 2, (0, 8)), (32, 3, (2, 6)),
                                             (300, 2, (0, 4))])
def test_grouped_matmul_kernel_matches_ragged_dot(rows, top_k, held):
    rng = np.random.default_rng(6)
    e, f, n_exp = 128, 128, 8
    lo, hi = held
    x = jnp.asarray(rng.normal(size=(rows, e)), jnp.bfloat16)
    w1, w3 = (jnp.asarray(rng.normal(size=(hi - lo, e, f)) * e ** -0.5,
                          jnp.bfloat16) for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(hi - lo, f, e)) * f ** -0.5,
                     jnp.bfloat16)
    ids = jnp.asarray(np.stack([rng.permutation(n_exp)[:top_k]
                                for _ in range(rows)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(rows, top_k)), jnp.float32)
    tm = gm.row_tile(rows)
    plan = gm.dispatch(ids, lo, hi, tm)
    outs = [gm.combine(gm.expert_mlp(x, plan, w1, w3, w2, tm, kernel=k),
                       plan, weights) for k in (True, False)]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               atol=2e-2, rtol=2e-2)
    # and both are the dense sum over the experts held
    x32 = np.asarray(x, np.float32)
    want = np.zeros((rows, e), np.float32)
    for t in range(rows):
        for j in range(top_k):
            ex = int(ids[t, j]) - lo
            if 0 <= ex < hi - lo:
                h = np.asarray(jax.nn.silu(x32[t] @ np.asarray(
                    w1[ex], np.float32))) * (x32[t] @ np.asarray(
                        w3[ex], np.float32))
                want[t] += float(weights[t, j]) * (
                    h @ np.asarray(w2[ex], np.float32))
    np.testing.assert_allclose(np.asarray(outs[1]), want, atol=6e-2,
                               rtol=6e-2)


def test_dropless_when_every_token_draws_one_expert():
    """No capacity: 200 tokens all routed to expert 5 all come back."""
    rng = np.random.default_rng(8)
    rows, e, f = 200, 128, 128
    x = jnp.asarray(rng.normal(size=(rows, e)), jnp.bfloat16)
    w1, w3 = (jnp.asarray(rng.normal(size=(8, e, f)) * e ** -0.5,
                          jnp.bfloat16) for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(8, f, e)) * f ** -0.5, jnp.bfloat16)
    ids = jnp.full((rows, 1), 5, jnp.int32)
    for kernel in (True, False):
        tm = gm.row_tile(rows)
        plan = gm.dispatch(ids, 0, 8, tm)
        assert int(plan.counts[5]) == rows and int(plan.counts.sum()) == rows
        out = gm.combine(gm.expert_mlp(x, plan, w1, w3, w2, tm, kernel),
                         plan, jnp.ones((rows, 1)))
        x32 = x.astype(jnp.float32)
        want = (jax.nn.silu(x32 @ w1[5].astype(jnp.float32))
                * (x32 @ w3[5].astype(jnp.float32))) @ w2[5].astype(
                    jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=6e-2, rtol=6e-2)
        assert float(jnp.abs(out).min(axis=-1).max()) > 0   # no zero row


def test_no_assignment_held_gives_zero():
    x = jnp.ones((16, 128), jnp.bfloat16)
    w = jnp.ones((2, 128, 128), jnp.bfloat16)
    ids = jnp.full((16, 2), 7, jnp.int32)       # all on absent experts
    for kernel in (True, False):
        plan = gm.dispatch(ids, 0, 2, 16)
        out = gm.combine(gm.expert_mlp(x, plan, w, w, w, 16, kernel), plan,
                         jnp.ones((16, 2)))
        assert int(plan.n_tiles[0]) == 0
        assert not np.asarray(out).any()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_gqa_page_walk_matches_the_gather(dtype, tol):
    rng = np.random.default_rng(9)
    b, hkv, g, d, page, n_pages = 3, 2, 3, 128, 8, 24
    kp, vp = (jnp.asarray(rng.normal(size=(n_pages, page, hkv * d)), dtype)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, hkv * g, d)), dtype)
    assert pa.paged_kernel_ok(q, kp)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:b * 5]
                        .reshape(b, 5), jnp.int32)
    pos = jnp.asarray([0, 17, 39], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(pa._paged_gqa_full(q, kp, vp, table, pos)),
        np.asarray(pa._xla_paged(q, kp, vp, table, pos)), atol=tol)
    ring = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:b * 3]
                       .reshape(b, 3), jnp.int32)
    for at in ([0, 17, 39], [5, 23, 100], [15, 16, 24]):
        pos = jnp.asarray(at, jnp.int32)
        np.testing.assert_allclose(
            np.asarray(pa._paged_gqa_window(q, kp, vp, ring, pos,
                                            window=16)),
            np.asarray(pa._xla_paged_window(q, kp, vp, ring, pos, 16)),
            atol=tol)


@pytest.mark.parametrize("s,window", [(16, 8), (512, None), (512, 64),
                                      (768, 300), (1024, 512)])
def test_prefill_attention_kernel_matches_xla(s, window):
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(1, s, 6, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, s, 2, 128)), jnp.float32)
            for _ in range(2))
    assert ak.prefill_attention_ok(q)
    np.testing.assert_allclose(
        np.asarray(ak.prefill_attention(q, k, v, window)),
        np.asarray(ak._xla_prefill_attention(q, k, v, window)), atol=1e-5)
