"""Continuous batching: concurrent decode streams share one slotted step,
and every stream's output is EXACTLY generate()'s, regardless of which
other requests are co-tenant (the correctness oracle)."""
import numpy as np

import jax
import jax.numpy as jnp

from mmlspark_tpu.models.generation import generate
from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.serving.batcher import ContinuousBatcher

import pytest


@pytest.fixture(scope="module")
def lm():
    model = transformer_lm(vocab_size=64, embed_dim=32, num_layers=2,
                           num_heads=2, max_len=48, dtype=jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 4), jnp.int32), train=False)
    variables = {c: v for c, v in variables.items() if c != "kvcache"}
    return model, variables


def _reference(model, variables, prompt, n):
    out = generate(model, variables, jnp.asarray(prompt)[None],
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


def test_streams_match_generate_under_co_tenancy(lm):
    model, variables = lm
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5], [3, 5, 8, 9],
               [2, 7, 1, 8, 2, 8]]
    n_new = [6, 9, 4, 7, 5]
    batcher = ContinuousBatcher(model, variables, max_slots=2).start()
    try:
        streams = [batcher.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, n_new)]
        got = [s.tokens() for s in streams]  # drains concurrently
    finally:
        batcher.stop()
    for p, n, toks in zip(prompts, n_new, got):
        assert toks == _reference(model, variables, p, n), (p, toks)


def test_slot_reuse_after_finish(lm):
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=1).start()
    try:
        # strictly serial through ONE slot: finish -> admit -> finish
        a = batcher.submit([7, 7], max_new_tokens=5).tokens()
        b = batcher.submit([9, 1, 2], max_new_tokens=6).tokens()
    finally:
        batcher.stop()
    assert a == _reference(model, variables, [7, 7], 5)
    assert b == _reference(model, variables, [9, 1, 2], 6)


def test_eos_ends_stream_early(lm):
    model, variables = lm
    ref = _reference(model, variables, [4, 4, 4], 10)
    eos = ref[2]  # pretend the 3rd greedy token is eos
    batcher = ContinuousBatcher(model, variables, max_slots=2).start()
    try:
        toks = batcher.submit([4, 4, 4], max_new_tokens=10,
                              eos_id=eos).tokens()
    finally:
        batcher.stop()
    assert toks == ref[:3]  # stops AT the eos token
    assert toks[-1] == eos


def test_stop_unblocks_consumers(lm):
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=1).start()
    s1 = batcher.submit([1, 2], max_new_tokens=40)   # hogs the slot a while
    s2 = batcher.submit([3, 4], max_new_tokens=40)   # queued behind it
    batcher.stop()
    # both streams must terminate (possibly truncated), not hang
    assert isinstance(s1.tokens(), list)
    assert isinstance(s2.tokens(), list)


def test_submit_validates(lm):
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=1)
    with pytest.raises(ValueError, match="empty"):
        batcher.submit([])
    with pytest.raises(ValueError, match="max_len"):
        batcher.submit([1] * 40, max_new_tokens=20)


def test_http_stream_reply_composition(lm):
    # the advertised serving shape: stream_reply(fn) where fn feeds the
    # shared batcher — concurrent HTTP clients ride one device batch and
    # each still gets exactly generate()'s tokens
    import http.client
    import threading

    from mmlspark_tpu.serving import read_stream

    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=4).start()

    def complete(row):
        toks = batcher.submit([int(t) for t in row["prompt"]],
                              max_new_tokens=int(row["n"]))
        for t in toks:
            yield f"{t} "

    query = (read_stream()
             .continuous_server(name="cb", path="/gen")
             .parse_request(schema=["prompt", "n"])
             .stream_reply(complete)
             .options(batch_timeout_ms=5.0)
             .start())
    prompts = [[3, 1, 4], [1, 5, 9, 2], [6, 5]]
    results = [None] * len(prompts)

    def client(i):
        import json as _json

        conn = http.client.HTTPConnection(query.service_info.host,
                                          query.service_info.port,
                                          timeout=30)
        conn.request("POST", "/gen", body=_json.dumps(
            {"prompt": prompts[i], "n": 5}).encode())
        results[i] = [int(t) for t in
                      conn.getresponse().read().decode().split()]
        conn.close()

    try:
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        query.stop()
        batcher.stop()
    for p, got in zip(prompts, results):
        assert got == _reference(model, variables, p, 5), (p, got)


def test_int8_cache_slots_match_generate_int8(lm):
    # int8 slot decode quantizes each written row exactly like generate's
    # scalar int8 path — outputs match bit for bit, at 4x slot density
    model, variables = lm
    prompts = [[3, 1, 4], [1, 5, 9, 2], [6, 5]]
    batcher = ContinuousBatcher(model, variables, max_slots=2,
                                kv_cache_dtype="int8").start()
    try:
        streams = [batcher.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.tokens() for s in streams]
    finally:
        batcher.stop()
    for p, toks in zip(prompts, got):
        want = generate(model, variables, jnp.asarray(p)[None],
                        max_new_tokens=6, kv_cache_dtype="int8")
        assert toks == np.asarray(want)[0, len(p):].tolist(), (p, toks)
    import pytest

    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ContinuousBatcher(model, variables, kv_cache_dtype="int4")


@pytest.mark.parametrize("mode", ["dense", "paged", "paged_spec"])
def test_randomized_staggered_soak(lm, draft_lm, mode):
    # 12 requests, random lengths/budgets, submitted from threads at
    # random times into 3 slots — every stream must still be exactly
    # generate()'s output (seeded: deterministic).  The paged and
    # paged+speculative configs run the SAME chaos through page
    # recycling / reservation deferral / per-slot block verification.
    import threading
    import time

    model, variables = lm
    kw = {}
    if mode != "dense":
        kw.update(paged=True, page_size=8, num_pages=10)
    if mode == "paged_spec":
        draft, dv = draft_lm
        kw.update(draft_model=draft, draft_variables=dv, gamma=3)
    rng = np.random.default_rng(42)
    jobs = [(rng.integers(0, 64, size=rng.integers(1, 9)).tolist(),
             int(rng.integers(2, 8))) for _ in range(12)]
    delays = rng.integers(0, 20, size=len(jobs))  # pre-drawn: Generator
    batcher = ContinuousBatcher(model, variables, max_slots=3, **kw).start()
    results = [None] * len(jobs)

    def submit(i):
        time.sleep(float(delays[i]) / 1000.0)
        p, n = jobs[i]
        results[i] = batcher.submit(p, max_new_tokens=n).tokens()

    try:
        threads = [threading.Thread(target=submit, args=(i,), daemon=True)
                   for i in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        batcher.stop()
    for (p, n), toks in zip(jobs, results):
        assert toks == _reference(model, variables, p, n), (p, n, toks)


def test_modern_stack_batcher(lm):
    # rope + MQA + int8 slots through the batcher: streams must equal
    # generate's int8 decode for the same modern-stack model
    from mmlspark_tpu.models.transformer import transformer_lm

    model = transformer_lm(vocab_size=32, embed_dim=32, num_layers=1,
                           num_heads=4, max_len=24, dtype=jnp.float32,
                           pos_emb="rope", num_kv_heads=1)
    variables = {c: v for c, v in model.init(
        {"params": jax.random.PRNGKey(1)},
        jnp.zeros((1, 4), jnp.int32)).items() if c != "kvcache"}
    prompts = [[3, 1, 4], [9, 8]]
    batcher = ContinuousBatcher(model, variables, max_slots=2,
                                kv_cache_dtype="int8").start()
    try:
        got = [batcher.submit(p, max_new_tokens=5).tokens()
               for p in prompts]
    finally:
        batcher.stop()
    for p, toks in zip(prompts, got):
        want = generate(model, variables, jnp.asarray(p)[None],
                        max_new_tokens=5, kv_cache_dtype="int8")
        assert toks == np.asarray(want)[0, len(p):].tolist(), (p, toks)


def test_generate_stream_one_call_endpoint(lm):
    # the packaged LM endpoint: read_stream().generate_stream(...) owns
    # the batcher (started with the query, stopped with it) and streams
    # generate()-exact tokens to concurrent clients
    import http.client
    import json as _json
    import threading

    from mmlspark_tpu.serving import read_stream

    model, variables = lm
    query = (read_stream()
             .continuous_server(name="gen1call", path="/lm")
             .parse_request(schema=["prompt"])
             .generate_stream(model, variables, max_new_tokens=5,
                              max_slots=2)
             .options(batch_timeout_ms=5.0)
             .start())
    prompts = [[3, 1, 4], [9, 8], [2, 2, 7, 5]]
    results = [None] * len(prompts)

    def client(i):
        conn = http.client.HTTPConnection(query.service_info.host,
                                          query.service_info.port,
                                          timeout=30)
        conn.request("POST", "/lm", body=_json.dumps(
            {"prompt": prompts[i]}).encode())
        results[i] = [int(t) for t in
                      conn.getresponse().read().decode().split()]
        conn.close()

    try:
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        query.stop()
    for p, got in zip(prompts, results):
        assert got == _reference(model, variables, p, 5), (p, got)
    # stop() also stopped the BATCHER, not just the servers
    assert not query.is_active()
    assert not query._batcher._running.is_set()
    assert not query._batcher._thread.is_alive()
    import pytest

    with pytest.raises(RuntimeError, match="stopped"):
        query._batcher.submit([1, 2], max_new_tokens=2)


def test_stream_text_never_splits_words():
    """ADVICE r3 (medium): a word split across BPE subword tokens must
    stream as ONE piece — the concatenated stream equals decode() of the
    raw ids, with spaces only at word boundaries."""
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.featurize.tokenizer import BPETokenizer

    corpus = Table({"text": ["hello world hello there",
                             "world hello there world"]})
    tok = BPETokenizer(vocab_size=18).fit(corpus)
    # a vocab this small leaves multi-token words (the advisor's repro)
    assert any(len(tok._encode_word(w)) > 1 for w in ("hello", "world"))
    model = transformer_lm(vocab_size=len(tok.vocab), embed_dim=32,
                           num_layers=2, num_heads=2, max_len=64,
                           dtype=jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 4), jnp.int32), train=False)
    variables = {c: v for c, v in variables.items() if c != "kvcache"}
    batcher = ContinuousBatcher(model, variables, max_slots=2).start()
    try:
        pieces = list(batcher.stream_text(tok, "hello world",
                                          max_new_tokens=10))
        ids = batcher.submit(tok.encode("hello world", append_eos=False),
                             max_new_tokens=10,
                             eos_id=tok.eos_id).tokens()
    finally:
        batcher.stop()
    assert pieces, "stream yielded nothing"
    assert all(" " not in p.rstrip() for p in pieces), pieces
    assert "".join(pieces).strip() == tok.decode(ids)


def test_prefill_shapes_bucketed(lm):
    """ADVICE r3: admission pads prompts to power-of-two buckets so the
    serving hot path compiles O(log max_len) prefill shapes — prompts of
    different lengths within a bucket must produce EXACT generate()
    outputs (the padded tail is causally invisible)."""
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=2).start()
    try:
        # lengths 1..6 all land in the 16-bucket; outputs must stay exact
        prompts = [[5], [3, 1], [2, 7, 1], [1, 5, 9, 2], [8] * 5, [4] * 6]
        streams = [batcher.submit(p, max_new_tokens=4) for p in prompts]
        got = [s.tokens() for s in streams]
    finally:
        batcher.stop()
    for p, toks in zip(prompts, got):
        assert toks == _reference(model, variables, p, 4), (p, toks)


# ------------------------------------------------------------- paged KV

def test_paged_streams_match_generate(lm):
    """Paged-KV exactness oracle: with page pools + page table, every
    stream's tokens are EXACTLY generate()'s, across admits/finishes that
    recycle pages between co-tenant streams."""
    model, variables = lm
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5], [3, 5, 8, 9],
               [2, 7, 1, 8, 2, 8], [9, 9, 1]]
    n_new = [6, 9, 4, 7, 5, 8]
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8, num_pages=13).start()
    try:
        streams = [batcher.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, n_new)]
        got = [s.tokens() for s in streams]
    finally:
        batcher.stop()
    for p, n, toks in zip(prompts, n_new, got):
        assert toks == _reference(model, variables, p, n), (p, toks)
    # every page went back to the free list (page 0 stays trash)
    assert sorted(batcher._free) == list(range(1, batcher._np))
    assert batcher._avail == batcher._np - 1


def test_paged_int8_matches_generate_int8(lm):
    """Paging composes with the int8 KV cache: pooled int8 rows + scales
    reproduce generate(kv_cache_dtype='int8') bit for bit."""
    import jax.numpy as jnp  # noqa: F811

    model, variables = lm
    prompts = [[4, 4, 2], [7, 1, 1, 3], [2, 9]]
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8, kv_cache_dtype="int8").start()
    try:
        streams = [batcher.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.tokens() for s in streams]
    finally:
        batcher.stop()
    for p, toks in zip(prompts, got):
        ref = np.asarray(generate(
            model, variables, jnp.asarray(p)[None], max_new_tokens=6,
            kv_cache_dtype="int8"))[0, len(p):].tolist()
        assert toks == ref, (p, toks, ref)


def test_paged_admission_defers_until_pages_free(lm):
    """A pool too small for two worst-case tenants serializes them (strict
    FIFO reservation) instead of corrupting pages — and both streams stay
    exact."""
    model, variables = lm
    # worst case per request: ceil((5 + 10) / 8) = 2 pages; pool of 3
    # usable pages fits ONE tenant at a time
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8, num_pages=4).start()
    try:
        a = batcher.submit([1, 2, 3, 4, 5], max_new_tokens=10)
        b2 = batcher.submit([6, 7, 8, 9, 1], max_new_tokens=10)
        got_a, got_b = a.tokens(), b2.tokens()
    finally:
        batcher.stop()
    assert got_a == _reference(model, variables, [1, 2, 3, 4, 5], 10)
    assert got_b == _reference(model, variables, [6, 7, 8, 9, 1], 10)


def test_paged_oversized_request_rejected(lm):
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=1, paged=True,
                                page_size=8, num_pages=3)
    import pytest

    with pytest.raises(ValueError, match="pages"):
        batcher.submit([1] * 20, max_new_tokens=20)  # needs 5 > 2 pages


# ------------------------------------------- speculative continuous batching

@pytest.fixture(scope="module")
def draft_lm(lm):
    """A smaller draft sharing the target's vocabulary — initialized from
    a DIFFERENT seed, so acceptance is imperfect and the rejection path
    actually runs."""
    model, _ = lm
    draft = transformer_lm(vocab_size=model.vocab_size, embed_dim=16,
                           num_layers=1, num_heads=2, max_len=48,
                           dtype=jnp.float32)
    dv = draft.init({"params": jax.random.PRNGKey(9)},
                    jnp.zeros((1, 4), jnp.int32), train=False)
    return draft, {c: v for c, v in dv.items() if c != "kvcache"}


def test_speculative_batcher_matches_generate(lm, draft_lm):
    """Speculative continuous batching oracle: with a draft proposing
    per-slot blocks, every co-tenant stream's tokens are EXACTLY the
    TARGET's greedy generate() — the draft only changes how many target
    forwards it takes."""
    model, variables = lm
    draft, dv = draft_lm
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5], [3, 5, 8, 9], [2, 7, 1]]
    n_new = [6, 9, 4, 7, 8]
    batcher = ContinuousBatcher(model, variables, max_slots=2,
                                draft_model=draft, draft_variables=dv,
                                gamma=3).start()
    try:
        streams = [batcher.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, n_new)]
        got = [st.tokens() for st in streams]
    finally:
        batcher.stop()
    for p, n, toks in zip(prompts, n_new, got):
        assert toks == _reference(model, variables, p, n), (p, toks)


def test_speculative_batcher_eos_and_paged(lm, draft_lm):
    """Speculation composes with paged KV and eos early-stop, outputs
    staying exact."""
    model, variables = lm
    draft, dv = draft_lm
    ref = _reference(model, variables, [4, 4, 4], 10)
    eos = ref[2]
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8, draft_model=draft,
                                draft_variables=dv, gamma=3).start()
    try:
        toks = batcher.submit([4, 4, 4], max_new_tokens=10,
                              eos_id=eos).tokens()
        more = [batcher.submit(p, max_new_tokens=6)
                for p in ([1, 2, 3], [9, 8, 7, 6])]
        got_more = [st.tokens() for st in more]
    finally:
        batcher.stop()
    assert toks == ref[:3] and toks[-1] == eos
    for p, g2 in zip([[1, 2, 3], [9, 8, 7, 6]], got_more):
        assert g2 == _reference(model, variables, p, 6), (p, g2)
    assert sorted(batcher._free) == list(range(1, batcher._np))


def test_speculative_perfect_draft_accepts_fully(lm):
    """With the TARGET as its own draft every proposal matches: rounds
    collapse to ~ceil(n/(gamma+1)) target forwards (counted via the
    verify-step positions), and outputs stay exact."""
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=1,
                                draft_model=model, draft_variables=variables,
                                gamma=3).start()
    ticks = {"n": 0}
    orig = batcher._speculative_tick

    def counting(active):
        ticks["n"] += 1
        return orig(active)

    batcher._speculative_tick = counting
    try:
        toks = batcher.submit([3, 1, 4], max_new_tokens=8).tokens()
    finally:
        batcher.stop()
    assert toks == _reference(model, variables, [3, 1, 4], 8)
    # 8 tokens: 1 from prefill + 7 speculative; perfect acceptance emits
    # gamma+1=4 per tick -> 2 ticks
    assert ticks["n"] <= 3, ticks["n"]


def test_speculative_submit_respects_gamma_headroom(lm, draft_lm):
    model, variables = lm
    draft, dv = draft_lm
    batcher = ContinuousBatcher(model, variables, max_slots=1,
                                draft_model=draft, draft_variables=dv,
                                gamma=4)
    with pytest.raises(ValueError, match="gamma"):
        # 40 + 5 fits max_len 48 plainly but not with gamma-4 lookahead
        batcher.submit([1] * 40, max_new_tokens=5)


def test_speculative_moe_requires_dropfree_capacity(lm):
    model, variables = lm
    moe = transformer_lm(vocab_size=64, embed_dim=32, num_layers=1,
                         num_heads=2, max_len=48, dtype=jnp.float32,
                         moe_experts=4, moe_capacity=1.25)
    with pytest.raises(ValueError, match="moe_capacity"):
        ContinuousBatcher(moe, variables, draft_model=model,
                          draft_variables=variables)


def test_generate_stream_one_call_paged_speculative(lm, draft_lm):
    """The one-call endpoint passes paging + speculation through to the
    batcher it owns — and streams stay generate()-exact."""
    import http.client
    import json as _json

    from mmlspark_tpu.serving import read_stream

    model, variables = lm
    draft, dv = draft_lm
    query = (read_stream()
             .continuous_server(name="gen1spec", path="/lm")
             .parse_request(schema=["prompt"])
             .generate_stream(model, variables, max_new_tokens=6,
                              max_slots=2, paged=True, page_size=8,
                              draft_model=draft, draft_variables=dv,
                              gamma=3)
             .options(batch_timeout_ms=5.0)
             .start())
    try:
        assert query._batcher.paged and query._batcher.draft_model is draft
        conn = http.client.HTTPConnection(query.service_info.host,
                                          query.service_info.port,
                                          timeout=60)
        conn.request("POST", "/lm", body=_json.dumps(
            {"prompt": [3, 1, 4]}).encode())
        got = [int(t) for t in conn.getresponse().read().decode().split()]
        conn.close()
    finally:
        query.stop()
    assert got == _reference(model, variables, [3, 1, 4], 6), got


# ------------------------------------- the cache is updated in place only

# what each mode's pools look like: (constructor options, speculative?)
POOL_MODES = {
    "dense": ({}, False),
    "paged": (dict(paged=True, page_size=8), False),
    "paged_int8": (dict(paged=True, page_size=8, kv_cache_dtype="int8"),
                   False),
    "paged_spec": (dict(paged=True, page_size=8, gamma=3), True),
    "dense_spec": (dict(gamma=3), True),
}


@pytest.mark.parametrize("mode", list(POOL_MODES))
def test_cache_buffers_are_consumed_by_every_program(lm, draft_lm, mode):
    """Every program that takes the cache takes it donated: after an
    admission and a tick, the buffers the batcher was built with are
    gone (their memory is the new cache's), for the target's pools and
    the draft's cache alike — and the streams are still generate()'s.
    Paged pools are flat [NP, page, Hkv*D] (scale pools [NP, page,
    Hkv])."""
    model, variables = lm
    kw, spec = POOL_MODES[mode]
    if spec:
        draft, dv = draft_lm
        kw = dict(kw, draft_model=draft, draft_variables=dv)
    batcher = ContinuousBatcher(model, variables, max_slots=2, **kw)
    old = jax.tree.leaves(batcher._cache)
    if spec:
        old += jax.tree.leaves(batcher._d_cache)
    if batcher.paged:
        hd = model.kv_heads * (model.embed_dim // model.num_heads)
        widths = {hd} | ({model.kv_heads}
                         if kw.get("kv_cache_dtype") else set())
        assert {leaf.shape[2:] for layer in batcher._cache
                for leaf in layer} == {(w,) for w in widths}
    batcher.start()
    try:
        prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5]]
        got = [st.tokens() for st in
               [batcher.submit(p, max_new_tokens=6) for p in prompts]]
    finally:
        batcher.stop()
    assert all(leaf.is_deleted() for leaf in old)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(batcher._cache))
    kv = kw.get("kv_cache_dtype")
    for p, toks in zip(prompts, got):
        ref = np.asarray(generate(
            model, variables, jnp.asarray(p)[None], max_new_tokens=6,
            kv_cache_dtype=kv))[0, len(p):].tolist()
        assert toks == ref, (p, toks, ref)


def test_prefix_registration_consumes_the_pools(lm):
    """register_prefix loads pages through the same donated program,
    inline on the caller's thread when no loop runs: the pools it found
    are consumed, the ones it leaves are live and serve the prefix."""
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8)
    old = jax.tree.leaves(batcher._cache)
    prefix = [7, 3, 1, 4, 1, 5, 9, 2, 6, 5]
    h = batcher.register_prefix(prefix)
    assert all(leaf.is_deleted() for leaf in old)
    batcher.start()
    try:
        toks = batcher.submit([8, 9], max_new_tokens=5, prefix=h).tokens()
    finally:
        batcher.stop()
    assert toks == _reference(model, variables, prefix + [8, 9], 5)


@pytest.fixture(scope="module")
def wide_lm():
    """Two heads of 64: the narrowest LM whose flat pools (H*D = 128)
    the page-walk kernel takes."""
    model = transformer_lm(vocab_size=64, embed_dim=128, num_layers=2,
                           num_heads=2, max_len=64, dtype=jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(1)},
                           jnp.zeros((1, 4), jnp.int32), train=False)
    return model, {c: v for c, v in variables.items() if c != "kvcache"}


@pytest.mark.parametrize("kv", [None, "int8"], ids=["f32", "int8"])
def test_paged_streams_through_the_page_walk_kernel(wide_lm, monkeypatch,
                                                    kv):
    """The batcher's paged step with the Pallas page walk in it (forced
    on the CPU, interpret mode; only the paged arm: the prefill keeps
    the XLA attention), over the batcher's own flat pools: streams still
    match generate() token for token."""
    from mmlspark_tpu.models import transformer
    from mmlspark_tpu.ops import paged_attention as pa

    model, variables = wide_lm
    real = transformer.default_attn(True)
    monkeypatch.setattr(transformer, "_single_tpu", lambda: True)
    monkeypatch.setattr(transformer, "default_attn", lambda causal: real)
    walked = []
    for name in ("_paged_pallas", "_paged_pallas_int8"):
        fn = getattr(pa, name)
        monkeypatch.setattr(pa, name, lambda *a, _fn=fn, _n=name:
                            (walked.append(_n), _fn(*a))[1])
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6, 5, 3, 5, 8, 9], [5]]
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8, kv_cache_dtype=kv).start()
    try:
        got = [st.tokens() for st in
               [batcher.submit(p, max_new_tokens=7) for p in prompts]]
    finally:
        batcher.stop()
    assert set(walked) == {"_paged_pallas_int8" if kv else "_paged_pallas"}
    monkeypatch.undo()
    for p, toks in zip(prompts, got):
        ref = np.asarray(generate(
            model, variables, jnp.asarray(p)[None], max_new_tokens=7,
            kv_cache_dtype=kv))[0, len(p):].tolist()
        assert toks == ref, (p, toks, ref)


# ------------------------------------------------------ prefix caching

def test_prefix_caching_streams_exact_and_pages_shared(lm):
    """Shared-prefix oracle: requests submitted as (prefix handle,
    suffix) must emit EXACTLY generate(prefix + suffix)'s tokens while
    their page tables point at the handle's shared pages."""
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8).start()
    try:
        prefix = [7, 3, 1, 4, 1, 5, 9, 2, 6, 5]          # 10 ids: 1 shared page
        h = batcher.register_prefix(prefix)
        shared_pages = list(batcher._prefixes[h]["pages"])
        assert batcher._prefixes[h]["shared"] == 1
        suffixes = [[8, 9], [2], [], [4, 4, 4, 4, 4, 4, 4]]
        streams = [batcher.submit(sfx, max_new_tokens=5, prefix=h)
                   for sfx in suffixes]
        # a non-prefix tenant rides along
        plain = batcher.submit([9, 9, 1], max_new_tokens=6)
        got = [s.tokens() for s in streams]
        got_plain = plain.tokens()
        # while draining, at least one live slot's table led with the
        # shared page (checked after: the handle's pages never moved)
        assert list(batcher._prefixes[h]["pages"]) == shared_pages
    finally:
        batcher.stop()
    for sfx, toks in zip(suffixes, got):
        ref = _reference(model, variables, prefix + sfx, 5)
        assert toks == ref, (sfx, toks, ref)
    assert got_plain == _reference(model, variables, [9, 9, 1], 6)


def test_prefix_pages_immutable_across_rounds(lm):
    """A second wave of requests over the SAME prefix must stay exact —
    any stray write into the shared pages by the first wave would
    corrupt the second."""
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8).start()
    try:
        prefix = list(range(1, 18))                       # 17 ids: 2 pages
        h = batcher.register_prefix(prefix)
        assert batcher._prefixes[h]["shared"] == 2
        first = [batcher.submit([5, int(i)], max_new_tokens=8, prefix=h)
                 for i in range(4)]
        _ = [s.tokens() for s in first]
        second = [batcher.submit([5, int(i)], max_new_tokens=8, prefix=h)
                  for i in range(4)]
        got2 = [s.tokens() for s in second]
    finally:
        batcher.stop()
    for i, toks in enumerate(got2):
        ref = _reference(model, variables, prefix + [5, i], 8)
        assert toks == ref, (i, toks, ref)


def test_prefix_release_and_accounting(lm):
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=1, paged=True,
                                page_size=8).start()
    try:
        h = batcher.register_prefix(list(range(1, 10)))   # 1 shared page
        st = batcher.submit([3], max_new_tokens=4, prefix=h)
        toks = st.tokens()
        assert toks == _reference(model, variables,
                                  list(range(1, 10)) + [3], 4)
        # all request-owned pages returned; the prefix page still held.
        # (the terminating None is enqueued BEFORE the loop thread frees
        # the pages — poll briefly instead of racing it)
        import time as _time

        for _ in range(100):
            if len(batcher._free) == batcher._np - 2:
                break
            _time.sleep(0.02)
        assert len(batcher._free) == batcher._np - 2
        batcher.release_prefix(h)
        assert sorted(batcher._free) == list(range(1, batcher._np))
        assert batcher._avail == batcher._np - 1
    finally:
        batcher.stop()


def test_prefix_release_refuses_while_in_use(lm):
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=1, paged=True,
                                page_size=8).start()
    try:
        h = batcher.register_prefix(list(range(1, 10)))
        st = batcher.submit([3] * 5, max_new_tokens=25, prefix=h)
        # refs increment at submit, so the refusal is deterministic even
        # before admission
        with pytest.raises(ValueError, match="active"):
            batcher.release_prefix(h)
        st.tokens()
    finally:
        batcher.stop()


def test_prefix_composes_with_speculation(lm, draft_lm):
    model, variables = lm
    draft, dv = draft_lm
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8, draft_model=draft,
                                draft_variables=dv, gamma=3).start()
    try:
        prefix = list(range(2, 13))                       # 11 ids
        h = batcher.register_prefix(prefix)
        streams = [batcher.submit([int(i)], max_new_tokens=7, prefix=h)
                   for i in range(3)]
        got = [s.tokens() for s in streams]
    finally:
        batcher.stop()
    for i, toks in enumerate(got):
        ref = _reference(model, variables, prefix + [i], 7)
        assert toks == ref, (i, toks, ref)


def test_prefix_page_aligned_empty_suffix(lm):
    """A page-aligned prefix + empty suffix exercises the rest=0 fast
    path: no suffix forward at all — the first token comes from the
    logits stored at registration, growth starts from zero owned pages,
    and the stream still equals generate(prefix)."""
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8).start()
    try:
        prefix = list(range(1, 17))                      # 16 ids: aligned
        h = batcher.register_prefix(prefix)
        assert batcher._prefixes[h]["shared"] == 2
        toks = batcher.submit([], max_new_tokens=6, prefix=h).tokens()
        # and a 3-page prefix whose suffix bucket pads PAST max_len
        # (st=24, rest=17 -> rb=32 -> block covers positions 24..55 with
        # max_len 48): the pad positions must hit the trash page, not
        # clamp onto the slot's LAST REAL page — regression for the
        # clamped-gather corruption bug
        p3 = list(range(1, 25))                          # 24 ids: 3 pages
        h3 = batcher.register_prefix(p3)
        assert batcher._prefixes[h3]["shared"] == 3
        long_sfx = [3] * 17                              # n=41, rest 17->32
        toks2 = batcher.submit(long_sfx, max_new_tokens=6,
                               prefix=h3).tokens()
    finally:
        batcher.stop()
    assert toks == _reference(model, variables, prefix, 6)
    assert toks2 == _reference(model, variables, p3 + long_sfx, 6)


def test_submit_ceiling_counts_all_prefixes(lm):
    """ADVICE r4 (medium): submit()'s capacity check must count pages
    held by EVERY registered prefix, not only the request's own — a
    request that passes a pool-wide check but can never satisfy the
    achievable budget would wedge the FIFO head forever."""
    model, variables = lm
    # pool: 5 usable pages (page 0 is trash); prefix holds 1
    batcher = ContinuousBatcher(model, variables, max_slots=1, paged=True,
                                page_size=8, num_pages=6)
    h = batcher.register_prefix(list(range(1, 10)))      # 1 shared page
    # worst = ceil((20+20)/8) = 5 own pages > 4 achievable (5 - 1 held)
    with pytest.raises(ValueError, match="can ever free"):
        batcher.submit([1] * 20, max_new_tokens=20)
    # with the prefix released the same request is admissible again
    batcher.release_prefix(h)
    st = batcher.submit([1] * 20, max_new_tokens=2)
    batcher.start()
    try:
        assert st.tokens() == _reference(model, variables, [1] * 20, 2)
    finally:
        batcher.stop()


def test_late_prefix_fails_neverfit_head_instead_of_wedging(lm):
    """ADVICE r4 (medium): a prefix registered AFTER a request passed
    submit()'s ceiling check can shrink the achievable budget below the
    request's reservation — the scheduler must fail that stream with an
    error, not defer it (and everyone behind it) forever."""
    model, variables = lm
    batcher = ContinuousBatcher(model, variables, max_slots=1, paged=True,
                                page_size=8, num_pages=6)
    # passes: worst 5 == achievable 5 (no prefixes yet); loop not started,
    # so the request sits in _pending
    doomed = batcher.submit([1] * 20, max_new_tokens=20)
    # inline registration (no loop yet) takes a page: achievable drops to 4
    batcher.register_prefix(list(range(1, 10)))
    behind = None
    batcher.start()
    try:
        with pytest.raises(RuntimeError, match="can ever free"):
            doomed.tokens()
        # the queue behind the failed head must still drain normally
        behind = batcher.submit([2] * 4, max_new_tokens=3).tokens()
    finally:
        batcher.stop()
    assert behind == _reference(model, variables, [2] * 4, 3)


def test_register_prefix_validates_draft_max_len(lm):
    """ADVICE r4 (low): a prefix longer than the DRAFT's max_len must
    fail register_prefix with a clear error (speculative mode prefills
    the full prompt into the dense draft cache), not die later in a
    numpy broadcast."""
    model, variables = lm
    draft = transformer_lm(vocab_size=model.vocab_size, embed_dim=16,
                           num_layers=1, num_heads=2, max_len=16,
                           dtype=jnp.float32)
    dv = draft.init({"params": jax.random.PRNGKey(3)},
                    jnp.zeros((1, 4), jnp.int32), train=False)
    dv = {c: v for c, v in dv.items() if c != "kvcache"}
    batcher = ContinuousBatcher(model, variables, max_slots=1, paged=True,
                                page_size=8, draft_model=draft,
                                draft_variables=dv, gamma=4)
    with pytest.raises(ValueError, match="draft"):
        batcher.register_prefix(list(range(1, 13)))      # 12+1+4 > 16


# -------------------------------------- decode-mode throughput regression

def test_decode_mode_throughput_ratios_regression():
    """Paged vs dense vs speculative RELATIVE throughput on the CPU
    backend, guarded by committed loose-tolerance ratio rows
    (benchmarks_serving.csv) — the no-chip canary for regressions in
    admission batching, page recycling, or the speculative round (a
    recompile-per-tick or page-thrash bug tanks these ratios 5-10x).
    Absolute tokens/sec are meaningless on a 1-core host; the paged HBM
    ratio IS exact (pool sizing is deterministic: 10 pages x 64 rows vs
    8 slots x 256 rows = 0.3125)."""
    import time as _time

    from test_benchmarks import assert_benchmark, load_benchmarks

    bench = load_benchmarks("benchmarks_serving.csv")
    model = transformer_lm(vocab_size=64, embed_dim=32, num_layers=2,
                           num_heads=2, max_len=256, dtype=jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 4), jnp.int32), train=False)
    variables = {c: v for c, v in variables.items() if c != "kvcache"}
    draft = transformer_lm(vocab_size=64, embed_dim=16, num_layers=1,
                           num_heads=2, max_len=256, dtype=jnp.float32)
    dv = draft.init({"params": jax.random.PRNGKey(9)},
                    jnp.zeros((1, 4), jnp.int32), train=False)
    dv = {c: v for c, v in dv.items() if c != "kvcache"}
    prompt = list(np.random.default_rng(0).integers(0, 64, size=16))
    n, n_new = 8, 24
    configs = {
        "dense": {},
        # worst-case 1 page/request at page 64: pool = 8*1 + trash + warm
        "paged": {"paged": True, "page_size": 64, "num_pages": 10},
        "spec": {"draft_model": draft, "draft_variables": dv, "gamma": 4},
    }

    def measure(kw):
        b = ContinuousBatcher(model, variables, max_slots=n, **kw).start()
        try:
            b.submit(prompt, max_new_tokens=2).tokens()   # compile warm
            t0 = _time.perf_counter()
            streams = [b.submit(prompt, max_new_tokens=n_new)
                       for _ in range(n)]
            total = sum(len(s.tokens()) for s in streams)
            dt = _time.perf_counter() - t0
            hbm = sum(int(leaf.size) * leaf.dtype.itemsize
                      for layer in b._cache for leaf in layer)
        finally:
            b.stop()
        return total / dt, hbm

    # throwaway pass: the first-ever run of each config pays XLA compiles
    # INSIDE the timed region (the 8-wide prefill bucket only compiles at
    # the first 8-stream burst) — ratios only mean anything steady-state
    for kw in configs.values():
        measure(kw)
    last = None
    for _attempt in range(2):  # single shared core: one re-measure allowed
        tps = {}
        hbm = {}
        for name, kw in configs.items():
            tps[name], hbm[name] = measure(kw)
        try:
            assert_benchmark(bench, "decode_paged_over_dense",
                             tps["paged"] / tps["dense"])
            assert_benchmark(bench, "decode_spec_over_dense",
                             tps["spec"] / tps["dense"])
            # deterministic pool sizing: two-sided against the committed
            # CSV row — an under-allocated pool (silently shrunk cache)
            # must fail just like an over-allocated one, and the CSV
            # stays the single arbiter a maintainer edits
            expected, prec, _hb = bench["decode_paged_hbm_ratio"]
            assert abs(hbm["paged"] / hbm["dense"] - expected) <= prec, (
                hbm, expected)
            return
        except AssertionError as e:
            last = e
            _time.sleep(1.0)
    raise last


# ------------------------------- serving across devices (tensor parallel)

def test_paged_batcher_on_tensor_parallel_target(lm, draft_lm):
    """The serving stack's scale-out composition (SURVEY §2.10; the
    TPU-native answer to HTTPSourceV2's cluster fan-out): the continuous
    batcher drives a tp=2-sharded TransformerLM on the virtual 8-device
    mesh — GSPMD shards the decode-step matmuls over 'model' while the
    page pools/tables stay replicated host-driven state.  Paged AND
    paged+speculative streams must equal the unsharded generate()."""
    from mmlspark_tpu.models.training import shard_params
    from mmlspark_tpu.parallel.mesh import MeshContext, make_mesh
    from mmlspark_tpu.parallel.sharding_rules import lm_tensor_parallel_rules

    model, variables = lm
    draft, dv = draft_lm
    mesh = make_mesh(data=jax.device_count() // 2, model=2)
    with MeshContext(mesh):
        tp_vars = {"params": shard_params(variables["params"], mesh,
                                          lm_tensor_parallel_rules)}
        prompts = [[2, 7, 1, 8], [5, 5], [9] * 11]
        for kw in ({"paged": True, "page_size": 8},
                   {"paged": True, "page_size": 8, "draft_model": draft,
                    "draft_variables": dv, "gamma": 3}):
            batcher = ContinuousBatcher(model, tp_vars, max_slots=2,
                                        **kw).start()
            try:
                streams = [batcher.submit(p, max_new_tokens=6)
                           for p in prompts]
                got = [s.tokens() for s in streams]
            finally:
                batcher.stop()
            for p, toks in zip(prompts, got):
                ref = _reference(model, variables, p, 6)
                assert toks == ref, (kw, p, toks, ref)
