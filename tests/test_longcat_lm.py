"""LongCatLM (models/longcat_lm.py) against the plain float32 reference of
the LongCat-Flash family (benchmarks/lib/reference_longcat.py), at a small
size on the CPU with seeded random weights: the forward, prefill then
decode through the one-pool latent cache, absorbed decode against
expanded attention, the shares of the routed experts with the identity
experts counted once, the batcher serving a one-pool cache kind, and the
kernels in interpret mode against their XLA compositions."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.models import longcat_lm, moe_lm
from mmlspark_tpu.models.longcat_lm import LongCatLM, latent_row_width
from mmlspark_tpu.models.transformer import _rope
from mmlspark_tpu.ops import attention_kernels as ak
from mmlspark_tpu.ops import paged_attention as pa
from mmlspark_tpu.serving.batcher import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the configuration file's `rehearse` preset with all 8 experts held,
# spelled as a config.json
CFG = {
    "hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
    "num_layers": 2, "num_attention_heads": 4, "kv_lora_rank": 24,
    "q_lora_rank": 32, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "qk_nope_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 8, "zero_expert_num": 4, "moe_topk": 3,
    "rms_norm_eps": 1e-5, "rope_theta": 10000000, "vocab_size": 128,
    "published": {"n_routed_experts": 8},
}


@pytest.fixture(scope="module")
def ref():
    """benchmarks/lib/reference_longcat.py by path."""
    spec = importlib.util.spec_from_file_location(
        "reference_longcat", os.path.join(ROOT, "benchmarks", "lib",
                                          "reference_longcat.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _model(held=8, max_len=64, dtype=jnp.float32):
    return LongCatLM.from_config(dict(CFG, n_routed_experts=held), max_len,
                                 dtype)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with a selection bias that is not zero, so that the
    choice by biased score and the weight by unbiased score differ."""
    p = _model().init(jax.random.PRNGKey(7),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    key = jax.random.PRNGKey(11)
    for i in range(CFG["num_layers"]):
        moe = p[f"layer{i}"]["moe"]
        moe["bias"] = jax.random.uniform(jax.random.fold_in(key, i),
                                         moe["bias"].shape, jnp.float32,
                                         -2e-2, 2e-2)
    return p


def _share(params, lo, hi):
    """The parameter tree of the chip that holds FFN experts [lo, hi)."""
    def cut(path, a):
        names = [p.key for p in path]
        if "moe" in names and names[-1] in ("w1", "w2", "w3"):
            return a[lo:hi]
        return a
    return jax.tree_util.tree_map_with_path(cut, params)


def _tokens(n, seed=3):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 128, n))


def test_forward_logits_match_the_reference(ref, params):
    toks = _tokens(40)
    got, _taps = _model().apply({"params": params}, toks[None])
    want = ref.logits(params, toks, ref.arch_of(CFG))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-4)


def test_the_selection_bias_changes_the_choice_and_not_the_weights(ref,
                                                                   params):
    arch = ref.arch_of(CFG)
    p = params["layer0"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(2), (64, 64))
    _r, probs, own, _d = ref._route(p, y, arch)
    _s, plain = jax.lax.top_k(probs, arch["top_k"])
    assert (np.sort(np.asarray(own), -1)
            != np.sort(np.asarray(plain), -1)).any()
    # weights are the unbiased scores times the scaling, not renormalised
    out = ref._routed(p, y, probs, own, arch, 0)
    w = 6.0 * np.take_along_axis(np.asarray(probs), np.asarray(own), -1)
    assert w.sum(-1).max() < 6.0 * 0.9
    assert np.isfinite(np.asarray(out)).all()


def test_half_held_forward_matches_the_reference_share(ref, params):
    toks = _tokens(24, seed=4)
    arch = ref.arch_of(CFG)
    for lo, hi in ((0, 4), (4, 8)):
        model = _model().clone(experts_held=(lo, hi))
        share = _share(params, lo, hi)
        got, _ = model.apply({"params": share}, toks[None])
        want = ref.logits(share, toks, arch, lo)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=2e-4)


def test_shares_add_up_to_the_uncut_layer(ref, params):
    """At 4 shares of 2 experts: the routed sums of all shares, with the
    identity experts' part (which every chip computes alike) counted
    once, equal the uncut reference's routed layer."""
    arch = ref.arch_of(CFG)
    p = params["layer1"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(3), (48, 64))
    _r, probs, top_e, _d = ref._route(p, y, arch)
    whole = ref._routed(p, y, probs, top_e, arch, 0)
    w = arch["scaling"] * jnp.take_along_axis(probs, top_e, -1)
    identity = jnp.sum(jnp.where(top_e >= arch["experts"], w, 0.0),
                       -1)[:, None] * y
    assert float(jnp.abs(identity).max()) > 0
    total = jnp.zeros_like(whole)
    for lo in range(0, 8, 2):
        layer = moe_lm._SparseMLP(
            num_experts=8, top_k=3, width=32, shared_width=0, scaling=6.0,
            held=(lo, lo + 2), dtype=jnp.float32, renormalise=False,
            choice_bias=True, zero_experts=4)
        mine = {k: (v[lo:lo + 2] if k in ("w1", "w2", "w3") else v)
                for k, v in p.items()}
        part = layer.apply({"params": mine}, y)
        # every share computes the identity experts' part: count it once
        total = total + part - identity
    np.testing.assert_allclose(np.asarray(total + identity),
                               np.asarray(whole), atol=2e-5)


@pytest.mark.parametrize("tokens", [48, 41, 20])
def test_chunked_tokens_give_the_unchunked_layer(params, tokens):
    """A call of more tokens than `token_chunk` goes through the experts
    a chunk at a time, the last chunk padded with rows on no expert: the
    same sums, the same statistics, whether or not the chunk divides the
    call (48 = 3 x 16; 41 and 20 do not)."""
    p = params["layer0"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(11), (tokens, 64))
    live = jnp.arange(tokens) % 5 != 0
    made = []
    for chunk in (0, 16):
        layer = moe_lm._SparseMLP(
            num_experts=8, top_k=3, width=32, shared_width=0, scaling=6.0,
            held=(2, 6), dtype=jnp.float32, renormalise=False,
            choice_bias=True, zero_experts=4, token_chunk=chunk)
        made.append(layer.apply({"params": {
            k: (v[2:6] if k in ("w1", "w2", "w3") else v)
            for k, v in p.items()}}, y, live, mutable=["stats"]))
    (whole, whole_stats), (chunked, chunked_stats) = made
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               atol=1e-5)
    # experts touched are counted a chunk: at least the whole call's
    for name, value in whole_stats["stats"].items():
        if name == "moe_experts_touched":
            assert int(chunked_stats["stats"][name]) >= int(value)
        else:
            assert int(chunked_stats["stats"][name]) == int(value), name


def test_rope_is_the_programs_convention(ref):
    x = jax.random.normal(jax.random.PRNGKey(1), (12, 3, 8))
    want = _rope(x[None], jnp.arange(12), 1e7)[0]
    np.testing.assert_allclose(np.asarray(ref.rope(x, 1e7)),
                               np.asarray(want), atol=1e-6)


def test_absorbed_decode_matches_expanded_attention(params):
    """decode_step over a latent pool (attention in the latent space)
    against the plain forward (attention expanded), position by
    position."""
    model = _model()
    toks = _tokens(21, seed=6)
    full, _ = model.apply({"params": params}, toks[None])
    page, width = 4, latent_row_width(24, 8)
    cache = tuple((jnp.zeros((9, page, width), jnp.float32),)
                  for _ in model.layer_kinds)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    for t in range(21):
        lg, cache = model.apply(
            {"params": params}, toks[None, t:t + 1], cache,
            jnp.asarray([t], jnp.int32), (table,), method=model.decode_step)
        np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                   np.asarray(full[0, t]), atol=2e-4)


@pytest.fixture(scope="module")
def served(params):
    """Seven requests over three slots (so slots are reused), page 4,
    contexts past page boundaries."""
    telemetry.reset_counters("serving.")
    model = _model()
    batcher = ContinuousBatcher(model, {"params": params}, max_slots=3,
                                paged=True, page_size=4)
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
    return batcher, prompts, replies, wants


def test_prefill_then_decode_matches_the_reference_forward(ref, params,
                                                           served):
    """Logits, not tokens: each served token lies at the reference's best
    logit of its step, to rounding."""
    _b, prompts, replies, wants = served
    arch = ref.arch_of(CFG)
    fwd = jax.jit(lambda t: ref.logits(params, t, arch))
    for prompt, reply, want in zip(prompts, replies, wants):
        assert len(reply) == want
        lg = np.asarray(fwd(jnp.asarray(prompt + reply)))
        for j, tok in enumerate(reply):
            row = lg[len(prompt) + j - 1]
            assert row.max() - row[tok] < 1e-3


def test_the_one_pool_kind_is_sized_from_the_model(served):
    batcher, *_ = served
    model = batcher.model
    assert model.cache_kinds == (("latent", None),)
    assert len(batcher._cache) == 2 * model.num_layers
    width = latent_row_width(24, 8)
    for pools in batcher._cache:
        assert [p.shape for p in pools] == [(batcher._np, 4, width)]
    assert batcher._win is None


def test_pages_grow_are_released_and_counted(served):
    batcher, prompts, replies, _w = served
    # every page back: admission, just-in-time growth and release agree
    assert sorted(batcher._free) == list(range(1, batcher._np))
    assert batcher._avail == batcher._np - 1
    assert not any(batcher._slot_pages)
    c = telemetry.counters()
    pairs = sum(len(p) * (len(p) + 1) // 2 for p in prompts)
    assert c["serving.batcher.prefill.attended.latent"] == pairs
    # decode step j of a request attends its n + j cached positions
    decode = sum(sum(len(p) + j + 1 for j in range(len(r) - 1))
                 for p, r in zip(prompts, replies))
    assert c["serving.batcher.attended.latent"] == pairs + decode
    assert c["serving.batcher.pages.latent"] > 0
    assert "serving.batcher.pages.full" not in c


def test_admission_tiles_are_counted(served, monkeypatch):
    """What the admission kernel's schedule visits for the prompts' own
    positions, beside the buckets' whole schedules, counted where the
    kernel runs: nothing off the TPU (the batcher holds no shapes to
    count at), nothing at this model's narrow heads, and at the cell's
    (64 heads of their own at q/k 192, v 128) a prompt of 5,000 in the
    8,192 bucket 55 of 136 and one of 3,000 in the 4,096 bucket 21 of
    36."""
    batcher, _prompts, *_ = served
    model = batcher.model
    assert model.attn_shapes == ((1, CFG["qk_nope_head_dim"]
                                  + CFG["qk_rope_head_dim"],
                                  CFG["v_head_dim"]),)
    assert batcher._attn_shapes == ()
    seen = {}
    monkeypatch.setattr(telemetry, "incr", lambda name, n=1: seen.__setitem__(
        name, seen.get(name, 0) + n))
    batcher._note_attn_tiles(5000, 8192)
    assert seen == {}
    monkeypatch.setattr(batcher, "_attn_shapes", model.attn_shapes)
    batcher._note_attn_tiles(5000, 8192)
    assert set(seen.values()) == {0}
    seen.clear()
    monkeypatch.setattr(batcher, "_attn_shapes", ((1, 192, 128),))
    batcher._note_attn_tiles(5000, 8192)
    assert seen == {"serving.batcher.prefill.attn_tiles": 55,
                    "serving.batcher.prefill.attn_tiles_bucket": 136}
    batcher._note_attn_tiles(3000, 4096)
    assert seen == {"serving.batcher.prefill.attn_tiles": 55 + 21,
                    "serving.batcher.prefill.attn_tiles_bucket": 136 + 36}


@pytest.mark.parametrize("lengths", [(5, 11), (16, 1), (9, 0)])
def test_first_tokens_do_not_see_the_buckets_padding(params, lengths):
    """An admission's logits at each prompt's last token, and the
    latent rows of its own positions, are the same whatever bucket the
    prompt is padded into and whatever the padding holds: attention
    runs over a row's first `lengths` positions (a pad row has none)."""
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
        for (a,), (b,) in zip(got[0][1], got[1][1]):
            np.testing.assert_allclose(np.asarray(a[row, :n]),
                                       np.asarray(b[row, :n]), atol=1e-5)


def test_routing_counters_ride_the_token_fetch(served):
    _b, prompts, replies, _w = served
    c = telemetry.counters()
    live = sum(len(p) for p in prompts) + sum(len(r) - 1 for r in replies)
    # every live token's top-3 falls on an FFN expert held (all 8 are)
    # or on an identity expert, in each of the two blocks
    assert (c["serving.moe.live_assignments"]
            + c["serving.moe.zero_assignments"]) == 2 * 3 * live
    assert 0 < c["serving.moe.zero_assignments"] < 2 * 3 * live


def test_teacher_force_replays_what_was_served(ref, params, served,
                                               monkeypatch):
    """Admissions replayed a piece of positions a program (16 here, so
    that the 21-token prompt's bucket of 32 takes two)."""
    from mmlspark_tpu.serving import batcher as batcher_module

    monkeypatch.setattr(batcher_module, "TAP_ROWS", 16)
    _b, prompts, replies, _w = served
    model = _model()
    batcher = ContinuousBatcher(model, {"params": params}, max_slots=3,
                                paged=True, page_size=4)
    pairs = list(zip(prompts, replies))[:3]
    out = batcher.teacher_force(
        pairs, taps=("router_input", "logits", "experts", "routed",
                     "mla_query", "mla_q_nope"))
    arch = ref.arch_of(CFG)
    for (prompt, reply), rec in zip(pairs, out):
        n, m = len(prompt), len(reply)
        assert (rec["logits"].argmax(-1) == np.asarray(reply)).all()
        want = np.asarray(ref.logits(params, jnp.asarray(prompt + reply),
                                     arch))[n - 1:n + m - 1]
        np.testing.assert_allclose(rec["logits"], want, atol=3e-4)
        assert "input" not in rec["routing"]
        assert rec["routing"]["experts"].shape == (2, n + m - 1, 3)
        # the decode steps' taps: one row a token fed back, 4 sublayers
        heads = min(4, longcat_lm.TAP_HEADS)
        assert rec["routing"]["mla_q_nope"].shape == (4, m - 1, heads * 16)
        taps = {k: jnp.asarray(v) for k, v in rec["routing"].items()}
        taps["input"] = taps["router_input"]
        for i in range(2):
            check = ref.layer_check(params[f"layer{i}"]["moe"],
                                    {k: v[i] for k, v in taps.items()
                                     if not k.startswith("mla")}, arch)
            assert float(check["router_err"].max()) < 1e-5
            assert not bool(check["differs"].any())
            err = np.sqrt(float(check["routed_sq"].sum())
                          / float(check["routed_ref_sq"].sum()))
            assert err < 1e-5
            assert (np.asarray(check["zero"])
                    == np.asarray(check["zero_ref"])).all()
        for j in range(4):
            err = ref.absorb_check(
                params[f"layer{j // 2}"][f"attn{j % 2}"],
                {k: taps[k][j] for k in ("mla_query", "mla_q_nope")}, arch)
            assert float(err.max()) < 1e-5
    assert sorted(batcher._free) == list(range(1, batcher._np))


def test_layer_checks_see_each_fault(ref, params):
    """The routed layer without its identity term, and an absorbed query
    rounded to bfloat16, each read far over what a sound layer reads."""
    arch = ref.arch_of(CFG)
    p = params["layer0"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(5), (32, 64))
    layer = moe_lm._SparseMLP(
        num_experts=8, top_k=3, width=32, shared_width=0, scaling=6.0,
        held=(0, 8), dtype=jnp.float32, renormalise=False, choice_bias=True,
        zero_experts=4)

    def taps_of():
        _out, kept = layer.apply({"params": p}, y, mutable=["routing",
                                                            "stats"])
        return {k: v[0] for k, v in kept["routing"].items()}

    def err(taps):
        c = ref.layer_check(p, taps, arch)
        return np.sqrt(float(c["routed_sq"].sum())
                       / float(c["routed_ref_sq"].sum()))

    assert err(taps_of()) < 1e-5
    sound = moe_lm._zero_experts_term
    moe_lm._zero_experts_term = lambda y, w, e, n: jnp.zeros(y.shape)
    try:
        assert err(taps_of()) > 0.05
    finally:
        moe_lm._zero_experts_term = sound
    q_nope = jax.random.normal(jax.random.PRNGKey(6), (10, 4 * 16))
    attn = params["layer0"]["attn0"]
    w_k = attn["wkvb"].reshape(24, 4, 32)[..., :16]
    q_abs = jnp.einsum("shd,rhd->shr", q_nope.reshape(10, 4, 16),
                       w_k) / np.sqrt(24.0)
    q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, 128 - 24)))
    for dtype, sound_at in ((jnp.float32, 1e-6), (jnp.bfloat16, 1e-4)):
        hi, lo = pa._mla_query_parts(q_abs, dtype)
        both = (hi.astype(jnp.float32) + lo.astype(jnp.float32))
        taps = {"mla_q_nope": q_nope, "mla_query": both.reshape(10, -1)}
        assert float(ref.absorb_check(attn, taps, arch).max()) < sound_at
    taps["mla_query"] = hi.astype(jnp.float32).reshape(10, -1)
    assert float(ref.absorb_check(attn, taps, arch).max()) > 1e-3


def test_unsupported_modes_are_refused(params):
    model = _model()
    for kw in (dict(paged=False), dict(paged=True, kv_cache_dtype="int8")):
        with pytest.raises(ValueError, match="LongCatLM is served over page"):
            ContinuousBatcher(model, {"params": params}, **kw)
    batcher = ContinuousBatcher(model, {"params": params}, max_slots=2,
                                paged=True, page_size=4)
    with pytest.raises(ValueError, match="BLOCK decode, which LongCatLM"):
        batcher.register_prefix(list(range(8)))
    cache = tuple((jnp.zeros((3, 4, 128), jnp.float32),)
                  for _ in model.layer_kinds)
    with pytest.raises(NotImplementedError, match="one-pool cache kind"):
        model.apply({"params": params}, jnp.zeros((1, 2), jnp.int32), cache,
                    jnp.zeros((1,), jnp.int32),
                    (jnp.zeros((1, 2), jnp.int32),),
                    method=model.decode_step)


# ---- the kernels, interpret mode against their XLA compositions ------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_latent_page_walk_matches_the_gather(dtype, tol):
    """Slots at position 0 (parked), inside their first chunk of pages,
    on a chunk boundary and several chunks in."""
    rng = np.random.default_rng(0)
    b, h, rank, rope, page, mp, n_pages = 4, 4, 128, 64, 16, 24, 60
    w = latent_row_width(rank, rope)
    assert w == 256
    pool = jnp.asarray(rng.normal(size=(n_pages, page, w)), dtype)
    table = np.zeros((b, mp), np.int32)
    pos = np.asarray([0, 37, pa._MLA_CHUNK * page - 1, 300], np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for i, p in enumerate(pos):
        if p:
            for j in range(p // page + 1):
                table[i, j] = free.pop()
    q = jnp.asarray(rng.normal(size=(b, h, w)), jnp.float32) * 0.2
    assert pa.mla_kernel_ok(pool, rank)
    got, read = pa.paged_mla_attention(q, pool, jnp.asarray(table),
                                       jnp.asarray(pos), rank)
    hi, lo = pa._mla_query_parts(q, dtype)
    want = pa._xla_paged_mla(hi, lo, pool, jnp.asarray(table),
                             jnp.asarray(pos), rank)
    assert got.shape == (b, h, rank)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)
    # what the walk multiplied is the query to 16 bits of mantissa
    np.testing.assert_allclose(np.asarray(read), np.asarray(q),
                               rtol=2e-5 if dtype == jnp.bfloat16 else 0)


def test_latent_walk_declines_what_it_cannot_tile():
    pool = jnp.zeros((4, 8, 128), jnp.float32)
    assert not pa.mla_kernel_ok(pool, 24)            # a latent off the lanes
    assert not pa.mla_kernel_ok(jnp.zeros((4, 4, 128)), 128)   # half a tile
    assert not pa.mla_kernel_ok(jnp.zeros((4, 8, 128), jnp.bfloat16), 128)
    got, _ = pa.paged_mla_attention(
        jnp.ones((1, 2, 128)), pool, jnp.zeros((1, 2), jnp.int32),
        jnp.zeros((1,), jnp.int32), 24)
    assert got.shape == (1, 2, 24)


@pytest.mark.parametrize("s", [16, 512])
def test_prefill_attention_kernel_takes_two_head_widths(s):
    """q/k heads of 192 against v heads of 128 (latent attention,
    expanded), the kernel in interpret mode against the XLA composition."""
    rng = np.random.default_rng(1)
    b, h = 1, 2
    q = jnp.asarray(rng.normal(size=(b, s, h, 192)), jnp.float32) * 0.3
    k = jnp.asarray(rng.normal(size=(b, s, h, 192)), jnp.float32) * 0.3
    v = jnp.asarray(rng.normal(size=(b, s, h, 128)), jnp.float32)
    assert ak.prefill_attention_ok(q, v)
    assert not ak.prefill_attention_ok(q[..., :64], v)
    got = ak.prefill_attention(q, k, v)
    want = ak._xla_prefill_attention(q, k, v, None)
    assert got.shape == (b, s, h, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
