"""Serving a REAL device model end-to-end: ImageFeaturizer behind
ServingServer's continuous-batching loop — the SparkServing continuous-
batched model endpoint configuration (docs/mmlspark-serving.md
pipeline-behind-HTTP examples)."""
import base64
import io
import json
import urllib.request

import numpy as np
import pytest
from PIL import Image

from mmlspark_tpu import LambdaTransformer, Table
from mmlspark_tpu.core.pipeline import Pipeline
from mmlspark_tpu.models.bundle import FlaxBundle
from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
from mmlspark_tpu.serving import ServingServer


@pytest.fixture(scope="module")
def bundle():
    import jax.numpy as jnp

    return FlaxBundle(
        "resnet18", {"num_classes": 10, "dtype": jnp.float32},
        input_shape=(32, 32, 3), seed=0,
    )


def _jpeg_b64(rng) -> str:
    arr = rng.integers(0, 255, (32, 32, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG")
    return base64.b64encode(buf.getvalue()).decode()


def _post(url: str, payload: dict) -> dict:
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def test_featurizer_served_continuous(bundle, rng):
    # decode b64 -> bytes column, featurize, reply with the feature vector
    stages = Pipeline(stages=[
        LambdaTransformer(fn=lambda t: t.with_column(
            "image", [base64.b64decode(v) for v in t["image_b64"]])),
        ImageFeaturizer(bundle=bundle, input_col="image",
                        output_col="features", batch_size=4),
        LambdaTransformer(fn=lambda t: t.with_column(
            "reply", [list(map(float, row[:4])) for row in t["features"]])),
    ])
    # all-transformer pipeline: fit is a pass-through yielding the model
    pipeline = stages.fit(Table({"image_b64": [_jpeg_b64(rng)]}))
    srv = ServingServer(model=pipeline, reply_col="reply",
                        name="feat", path="/featurize", max_batch=8)
    info = srv.start()
    try:
        url = f"http://{info.host}:{info.port}/featurize"
        payloads = [{"image_b64": _jpeg_b64(rng)} for _ in range(6)]
        replies = [_post(url, p) for p in payloads]
        assert all(len(r["reply"]) == 4 for r in replies)
        # server reply must equal a direct transform of the same bytes
        direct = pipeline.transform(
            Table({"image_b64": [p["image_b64"] for p in payloads]}))
        for got, want in zip(replies, direct["reply"]):
            np.testing.assert_allclose(got["reply"], want, rtol=1e-4,
                                       atol=1e-4)
    finally:
        srv.stop()


def test_language_model_served_with_generation():
    """An LLM-style endpoint: prompt token ids in, KV-cache-generated
    continuation out — generation.generate wrapped in a LambdaTransformer
    behind the continuous-batching server (the generation module's stated
    serving contract)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.generation import generate
    from mmlspark_tpu.models.transformer import transformer_lm

    model = transformer_lm(vocab_size=64, embed_dim=32, num_layers=1,
                           num_heads=2, max_len=32, dtype=jnp.float32)
    toks0 = jnp.zeros((1, 4), jnp.int32)
    variables = model.init({"params": jax.random.PRNGKey(0)}, toks0,
                           train=False)

    def serve_fn(t: Table) -> Table:
        # a drained batch mixes prompt lengths: group by length (static
        # shapes per generate call, like the featurizer's shape groups)
        prompts = [np.asarray(p, np.int32) for p in t["prompt"]]
        groups: dict = {}
        for i, p in enumerate(prompts):
            groups.setdefault(len(p), []).append(i)
        results = [None] * len(prompts)
        for _n, idxs in groups.items():
            out = generate(model, variables,
                           jnp.asarray(np.stack([prompts[i] for i in idxs])),
                           max_new_tokens=6)
            for i, row in zip(idxs, np.asarray(out)):
                results[i] = row.tolist()
        return t.with_column("completion", results)

    srv = ServingServer(model=LambdaTransformer(fn=serve_fn),
                        reply_col="completion", name="lm", path="/generate",
                        batch_timeout_ms=5.0)
    info = srv.start()
    try:
        r = _post(info.url, {"prompt": [3, 1, 4, 1]})
        comp = r["completion"]
        assert comp[:4] == [3, 1, 4, 1] and len(comp) == 10
        # deterministic greedy decode: same prompt, same continuation
        r2 = _post(info.url, {"prompt": [3, 1, 4, 1]})
        assert r2["completion"] == comp

        # concurrent ragged-length clients: the batch loop may drain them
        # into ONE batch — the length-grouped serve_fn must handle it
        import threading

        got = {}

        def client(name, prompt):
            got[name] = _post(info.url, {"prompt": prompt})["completion"]

        threads = [
            threading.Thread(target=client, args=("a", [3, 1, 4, 1])),
            threading.Thread(target=client, args=("b", [5, 9])),
            threading.Thread(target=client, args=("c", [2, 6, 5])),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive(), "serving request hung"
        assert got["a"] == comp            # same prompt -> same result
        assert got["b"][:2] == [5, 9] and len(got["b"]) == 8
        assert got["c"][:3] == [2, 6, 5] and len(got["c"]) == 9
    finally:
        srv.stop()


# ---- the decode loop one step ahead (serving/batcher.py) -------------------
# A mixed run on fewer slots than requests, the loop driven by hand so that
# every count is the script's: (prompt length, max_new_tokens or None for
# "up to max_len", index of the reply token that is the request's eos_id or
# None).  The first is the longest and runs beside all the others, so a
# step is dispatched every iteration and pages (and a ring) are crossed
# with a step in flight; the rest queue for slots that have just come back.
MIXED = [(20, None, None), (5, 12, None), (7, 10, 3), (3, 4, None),
         (9, 5, None), (6, 1, None), (4, 6, 0), (11, 3, None)]


def mixed_requests(rng, vocab, max_len, reference):
    """MIXED as [(prompt, max_new_tokens, eos_id, the reply `reference`
    (prompt, n -> n greedy tokens) implies)]."""
    out = []
    for n, m, eos_at in MIXED:
        prompt = rng.integers(1, vocab, size=n).tolist()
        m = max_len - n if m is None else m
        want = reference(prompt, m)
        eos = None
        if eos_at is not None:
            eos = want[eos_at]
            want = want[:want.index(eos) + 1]
        out.append((prompt, m, eos, want))
    return out


def run_mixed(batcher, requests):
    """Submit every request at once and tick until the last reply closed.
    -> (replies, decode steps dispatched)."""
    from mmlspark_tpu.core import telemetry

    def fills():
        return telemetry.histogram(
            "serving.batcher.batch_fill").snapshot()["count"]

    before = fills()
    streams = [batcher.submit(p, max_new_tokens=m, eos_id=eos)
               for p, m, eos, _want in requests]
    for _ in range(500):
        if not (batcher._buffer or batcher._intake.depth()
                or any(r is not None for r in batcher._live)):
            break
        batcher._tick()
    else:
        raise AssertionError("the mixed run never drained")
    return [s.tokens() for s in streams], fills() - before


def late_ends(requests):
    """Requests an eos_id ends short of their limit: each has one dead
    row in the step that was in flight when the eos came back."""
    return sum(1 for _p, m, eos, want in requests
               if eos is not None and len(want) < m)


@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.transformer import transformer_lm

    model = transformer_lm(vocab_size=64, embed_dim=32, num_layers=2,
                           num_heads=2, max_len=48, dtype=jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 4), jnp.int32), train=False)
    return model, {c: v for c, v in variables.items() if c != "kvcache"}


@pytest.fixture(scope="module", params=["paged", "dense"])
def mixed_lm(request, tiny_lm):
    """MIXED through the TransformerLM arm, over page pools and in dense
    slot mode, with what the loop counted."""
    import jax.numpy as jnp

    from mmlspark_tpu.core import telemetry
    from mmlspark_tpu.models.generation import generate
    from mmlspark_tpu.serving import batcher as batcher_mod
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    model, variables = tiny_lm

    def reference(prompt, n):
        out = generate(model, variables, jnp.asarray(prompt)[None],
                       max_new_tokens=n)
        return np.asarray(out)[0, len(prompt):].tolist()

    requests = mixed_requests(np.random.default_rng(3), 64, model.max_len,
                              reference)
    names = (batcher_mod.TICK_OVERLAPPED, batcher_mod.TICK_LATE_DISCARDS)
    before = {n: telemetry.counters().get(n, 0) for n in names}
    batcher = ContinuousBatcher(model, variables, max_slots=3,
                                paged=request.param == "paged", page_size=8)
    try:
        replies, steps = run_mixed(batcher, requests)
    finally:
        batcher.stop()
    counted = {n: telemetry.counters().get(n, 0) - before[n] for n in names}
    return batcher, requests, replies, steps, counted


def test_mixed_replies_are_generates(mixed_lm):
    """Token for token and in length: an eos_id hit mid-flight, a reply
    that ends at max_len, slots re-admitted the tick they came back."""
    _b, requests, replies, _steps, _counted = mixed_lm
    assert [len(r) for r in replies] == [len(w) for *_x, w in requests]
    assert replies == [w for *_x, w in requests]
    prompt, m, _eos, want = requests[0]
    assert len(prompt) + len(want) == 48 and len(want) == m


def test_mixed_run_counts_overlap_and_dead_rows(mixed_lm):
    from mmlspark_tpu.serving import batcher as batcher_mod

    _b, requests, _replies, steps, counted = mixed_lm
    # the longest request decodes beside every other, so each step but
    # the first was dispatched with the one before it unfetched
    assert steps == len(requests[0][3]) - 1
    assert counted[batcher_mod.TICK_OVERLAPPED] == steps - 1
    assert counted[batcher_mod.TICK_LATE_DISCARDS] == late_ends(requests) == 2


def test_mixed_run_returns_every_page_and_reservation(mixed_lm):
    batcher = mixed_lm[0]
    assert all(r is None for r in batcher._live)
    assert not batcher._flight and not batcher._inflight.any()
    assert not batcher._pos.any()
    if batcher.paged:
        assert sorted(batcher._free) == list(range(1, batcher._np))
        assert batcher._avail == batcher._np - 1
        assert not batcher._table.any()
        assert batcher._slot_reserved == [0] * batcher.max_slots


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_stop_fetches_the_step_in_flight(tiny_lm, paged):
    """A loop stopped between two iterations has a step dispatched and
    not fetched: stop() hands its tokens on, leaves no thread, and the
    host's mirrors agree with what the device wrote."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.generation import generate
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    model, variables = tiny_lm
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=paged,
                                page_size=8, idle_sleep_s=0.0005).start()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (6, 13)]
    streams = [batcher.submit(p, max_new_tokens=30) for p in prompts]
    heads = [[next(it) for _ in range(4)] for it in map(iter, streams)]
    batcher.stop()
    assert not batcher._thread.is_alive()
    assert not batcher._flight and not batcher._inflight.any()
    for prompt, stream, head, slot in zip(prompts, streams, heads, (0, 1)):
        got = head + stream.tokens()
        want = np.asarray(generate(
            model, variables, jnp.asarray(prompt)[None],
            max_new_tokens=30))[0, len(prompt):].tolist()
        assert 4 <= len(got) < 30 and got == want[:len(got)]
        # every token handed on has its row behind it, and one more is
        # due at the position the next dispatch would write
        assert batcher._pos[slot] == len(prompt) + len(got) - 1
        assert batcher._tok[slot] == got[-1]
