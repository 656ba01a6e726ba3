"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process that touches JAX once, finds a TPU or FAILS, and drives the
three models' main paths through the entry points a user would call, at
published widths, each phase compared with a plain reference:

  featurize  ImageFeaturizer(ResNet-50).transform over seeded JPEGs,
             fused-resize kernel vs the same featurizer at use_pallas=False
             (since PR 29 the JPEGs are benchmarks/lib/images.jpeg_blobs'
             photograph-like pixels, not uniform noise: a max_diff or a
             decode time from before then was read on other input)
  vit        ViT-B/16 forward through default_attn (S=196 pads to 256)
             vs the same weights on full_attention
  lm_train   transformer_lm d768/L12 through make_lm_train_epoch (flash
             forward + backward kernels) vs full_attention
  admit_attn the serving cells' admission flash forward (grouped heads with
             and without a window, and q/k 192 over v 128) at ragged
             prompt lengths vs its XLA composition
  lm_serve   the README's one-call LM endpoint (paged continuous batching
             over loopback HTTP) vs models.generation.generate

`--chips 4` runs ONLY the multi-chip phase: make_lm_train_step_3d on
MeshPlan(data=2, model=2, pipe=1) against the one-device step.

Every phase prints one JSON line as it finishes, with what it spent
compiling or fetching executables and the persistent cache's answers
(the compile sentry's totals); the first failure stops the run with a
non-zero exit.  A `setup_programs` line follows: the sentry's start-up
report of the programs that cost set-up most.  The LAST stdout line of
a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

There is no CPU mode: without an accelerator the script exits non-zero
and prints no result.  The phases are plain functions of a size preset so
tests/test_chip_smoke.py can rehearse them tiny on the CPU.  Numbers
printed here are set-up facts (compile seconds, bytes, counts), never
performance claims.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# One LM width for lm_train, lm_serve and lm3d: a d768/L12 GPT.
_LM_FULL = dict(vocab_size=8192, embed_dim=768, num_layers=12, num_heads=12,
                max_len=1024)
_LM_TINY = dict(vocab_size=64, embed_dim=32, num_layers=2, num_heads=2,
                max_len=32)

# Every shape the phases run at.  tests/test_aot_tpu_compile.py compiles
# the "full" kernels for a described v5e from THIS table, so the two
# cannot drift apart.
SIZES = {
    "full": {
        "featurize": dict(builder="resnet50", n=256, side=224,
                          sizes=((256, 256), (224, 224), (320, 240)),
                          batch_size=64),
        "vit": dict(builder="vit_base", batch=128, side=224),
        "lm": _LM_FULL,
        "lm_train": dict(batch=16, seq=1024, steps=4),
        # (query heads, KV heads, q/k width, v width, window): Laguna's
        # full and window layers, LongCat's expanded latent heads
        "admit_attn": dict(bucket=2048, lengths=(2048, 1100),
                           shapes=((48, 8, 128, 128, None),
                                   (72, 8, 128, 128, 512),
                                   (64, 64, 192, 128, None))),
        "lm_serve": dict(prompt_lens=(5, 12, 20, 40), copies=2, max_new=16,
                         max_slots=8, page_size=64),
        "lm3d": dict(accum=2, micro=2, mb=4, seq=1024, steps=2),
    },
    "tiny": {
        "featurize": dict(builder="convnet_cifar", n=8, side=32,
                          sizes=((40, 48), (32, 32)), batch_size=8),
        "vit": dict(builder="vit_tiny", batch=1, side=32),
        "lm": _LM_TINY,
        "lm_train": dict(batch=8, seq=32, steps=2),
        "admit_attn": dict(bucket=32, lengths=(32, 13),
                           shapes=((4, 2, 128, 128, None),
                                   (6, 2, 128, 128, 8),
                                   (2, 2, 192, 128, None))),
        "lm_serve": dict(prompt_lens=(3, 18), copies=2, max_new=3,
                         max_slots=2, page_size=8),
        "lm3d": dict(accum=1, micro=2, mb=2, seq=32, steps=2),
    },
}

_COLLECTIVES = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\(")


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _kernels_expected() -> bool:
    """Mosaic kernels are in a program only when it targets TPU devices;
    the CPU rehearsal runs them in interpret mode (no custom call)."""
    from mmlspark_tpu.ops.pallas_kernels import on_tpu

    return on_tpu()


def _rel_diff(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-12))


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _lm(cfg, dtype, attn_fn=None):
    from mmlspark_tpu.models.transformer import transformer_lm

    return transformer_lm(dtype=dtype, attn_fn=attn_fn, **cfg)


def _causal_dense(q, k, v):
    from mmlspark_tpu.parallel.ring_attention import full_attention

    return full_attention(q, k, v, causal=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_featurize(size: dict, seed: int) -> dict:
    import jax
    import numpy as np

    from benchmarks.lib.images import jpeg_blobs
    from mmlspark_tpu import Table, native
    from mmlspark_tpu.core import telemetry
    from mmlspark_tpu.io.feed import FEED_TELEMETRY, FeedTelemetry
    from mmlspark_tpu.models.bundle import FlaxBundle
    from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
    from mmlspark_tpu.models.tpu_model import ImagePreprocess

    cfg = size["featurize"]
    # what runs is built from the files git would commit, never from an
    # untracked .so that happens to lie on disk
    if not native.build(force=True):
        raise RuntimeError("native lib failed to build from src/native.cpp")
    if not native.jpeg_available():
        raise RuntimeError("native lib built without libjpeg: the streaming "
                           "decode path would not run")
    side = cfg["side"]
    table = Table({"image": jpeg_blobs(cfg["n"], cfg["sizes"], seed)})
    bundle = FlaxBundle(cfg["builder"], {"num_classes": 1000},
                        input_shape=(side, side, 3), seed=seed)

    def featurizer(**kw):
        return ImageFeaturizer(bundle=bundle, input_col="image",
                               output_col="features",
                               batch_size=cfg["batch_size"], **kw)

    # evidence: the fused resize kernel is IN the compiled forward of a
    # resized shape group (the 224x224 group is a plain cast+normalize)
    feat = featurizer()
    model = feat._model_for(bundle, "image")
    dev_vars, jitted, _mesh = model._executor(bundle,
                                              model._fetch_name(bundle))
    h, w = cfg["sizes"][0]
    chunk = jax.ShapeDtypeStruct((cfg["batch_size"], h, w, 3), np.uint8)
    custom_calls = _custom_calls(jitted.lower(dev_vars, chunk).compile())

    degraded_before = dict(telemetry.counters("feed."))
    since = FEED_TELEMETRY.snapshot()
    got = np.asarray(feat.transform(table)["features"])
    feed = FeedTelemetry.summarize(FEED_TELEMETRY.delta(since))
    ref = np.asarray(featurizer(use_pallas=False).transform(table)["features"])
    now = telemetry.counters("feed.")
    degraded = {k: now.get(k, 0) - degraded_before.get(k, 0)
                for k in ("feed.degraded", "feed.shard_degraded")}

    # the kernel alone against the XLA composition, on one real chunk
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 256, (8, h, w, 3), dtype=np.uint8)
    mean, std = [103.53, 116.28, 123.675], [57.375, 57.12, 58.395]
    pre_diff = float(np.max(np.abs(
        np.asarray(ImagePreprocess(side, side, mean, std)(x))
        - np.asarray(ImagePreprocess(side, side, mean, std,
                                     use_pallas=False)(x)))))

    diff = _rel_diff(got, ref)
    tol, pre_tol = 5e-2, 2e-2   # bf16 backbone; 1 uint8 LSB = 1/57 = 0.0175
    ok = (got.shape == (cfg["n"], ref.shape[1]) and bool(np.isfinite(got).all())
          and diff <= tol and pre_diff <= pre_tol
          and not any(degraded.values())
          and (custom_calls >= 1) == _kernels_expected())
    return {
        "ok": ok,
        "compared": "pooled features vs use_pallas=False (max rel diff); "
                    "resize kernel vs XLA on one chunk (max abs diff)",
        "max_diff": diff, "tol": tol,
        "pre_max_abs_diff": pre_diff, "pre_tol": pre_tol,
        "features_shape": list(got.shape),
        "custom_calls": custom_calls, "kernels_expected": _kernels_expected(),
        "decoder": "native-libjpeg",
        "feed_degraded": bool(degraded["feed.degraded"]),
        "feed_shard_degraded": bool(degraded["feed.shard_degraded"]),
        "h2d_path": feed["h2d_path"],
    }


def phase_vit(size: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.bundle import FlaxBundle
    from mmlspark_tpu.models.transformer import _single_tpu
    from mmlspark_tpu.parallel.ring_attention import full_attention

    cfg = size["vit"]
    side = cfg["side"]
    bundle = FlaxBundle(cfg["builder"], {"num_classes": 1000},
                        input_shape=(side, side, 3), seed=seed)
    dev_vars = jax.device_put(jax.tree.map(
        lambda a: jnp.asarray(a, jnp.bfloat16), bundle.variables))
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(cfg["batch"], side, side, 3)), jnp.bfloat16)
    dense = bundle.module.clone(
        attn_fn=lambda q, k, v: full_attention(q, k, v, causal=False))

    compiled = jax.jit(lambda v, x: bundle.apply(v, x)["pool"]).lower(
        dev_vars, x).compile()
    custom_calls = _custom_calls(compiled)
    got = np.asarray(compiled(dev_vars, x))
    ref = np.asarray(jax.jit(
        lambda v, x: dense.apply(v, x, train=False)[1]["pool"])(dev_vars, x))

    diff, tol = _rel_diff(got, ref), 5e-2
    layers = bundle.module.num_layers
    ok = (got.shape == ref.shape and bool(np.isfinite(got).all())
          and diff <= tol
          and custom_calls == (layers if _kernels_expected() else 0))
    return {
        "ok": ok,
        "compared": "pool vs same weights on full_attention (max rel diff)",
        "max_diff": diff, "tol": tol, "pool_shape": list(got.shape),
        "custom_calls": custom_calls, "custom_calls_expected": layers,
        "kernels_expected": _kernels_expected(),
        "attn_branch": "pallas" if _single_tpu() else "xla",
    }


def phase_lm_train(size: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mmlspark_tpu.models.training import make_lm_train_epoch
    from mmlspark_tpu.models.transformer import _single_tpu
    from mmlspark_tpu.ops.attention_kernels import fused_attention

    cfg, lm = size["lm_train"], size["lm"]
    b, s, steps = cfg["batch"], cfg["seq"], cfg["steps"]
    model = _lm(dict(lm, max_len=s), jnp.bfloat16)
    rng = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(rng, (steps, b, s), 0, lm["vocab_size"],
                                jnp.int32)
    params = jax.jit(lambda r, t: model.init(r, t)["params"])(rng, tokens[0])
    opt = optax.adam(3e-4)
    opt_state = jax.jit(opt.init)(params)

    epoch = make_lm_train_epoch(model, opt, donate=False)
    compiled = epoch.lower(params, opt_state, tokens).compile()
    custom_calls = _custom_calls(compiled)
    losses = np.asarray(compiled(params, opt_state, tokens)[2])

    # the first step's loss is a pure forward at the initial weights: the
    # dense-attention epoch at this width needs 15.9 GB (described-device
    # memory analysis) and does not fit a 16 GB chip beside this one
    dense = _lm(dict(lm, max_len=s), jnp.bfloat16, _causal_dense)

    def first_loss(p, toks):
        logits, _ = dense.apply({"params": p}, toks)
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), toks[:, 1:]))

    ref_loss = float(jax.jit(first_loss)(params, tokens[0]))

    # ... so the backward kernels meet their reference one layer at a
    # time, at the step's own attention shape
    h = lm["num_heads"]
    d = lm["embed_dim"] // h
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
               for kk in jax.random.split(rng, 3))

    def grads(attn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))(q, k, v)

    g_kernel = grads(lambda q, k, v: fused_attention(q, k, v, True))
    g_dense = grads(_causal_dense)
    grad_diff = max(_rel_diff(a.astype(jnp.float32), r.astype(jnp.float32))
                    for a, r in zip(g_kernel, g_dense))

    loss_diff = abs(float(losses[0]) - ref_loss)
    tol, grad_tol = 2e-2, 5e-2
    expected = 2 * lm["num_layers"]   # forward + fused backward per layer
    ok = (bool(np.isfinite(losses).all()) and loss_diff <= tol
          and grad_diff <= grad_tol
          and custom_calls == (expected if _kernels_expected() else 0))
    return {
        "ok": ok,
        "compared": "first-step loss vs attn_fn=full_attention (abs diff); "
                    "fused_attention grads vs dense at the step's shape "
                    "(max rel diff)",
        "max_diff": loss_diff, "tol": tol,
        "attn_grad_max_rel_diff": grad_diff, "attn_grad_tol": grad_tol,
        "losses": [float(x) for x in losses], "ref_first_loss": ref_loss,
        "custom_calls": custom_calls, "custom_calls_expected": expected,
        "kernels_expected": _kernels_expected(),
        "attn_branch": "pallas" if _single_tpu() else "xla",
        "peak_bytes_in_use": _peak_bytes(),
    }


def phase_admit_attn(size: dict, seed: int) -> dict:
    """The admission flash forward at the serving cells' head shapes,
    rows of ragged length in one bucket, against its XLA composition;
    the bucket's padding must come back exactly zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.transformer import _single_tpu
    from mmlspark_tpu.ops import attention_kernels as ak

    cfg = size["admit_attn"]
    s, lengths = cfg["bucket"], jnp.asarray(cfg["lengths"], jnp.int32)
    b = len(cfg["lengths"])
    diffs, calls, padding_zero = [], 0, True
    for i, (h, hkv, d, dv, window) in enumerate(cfg["shapes"]):
        q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
                   for kk, shape in zip(
                       jax.random.split(jax.random.PRNGKey(seed + i), 3),
                       ((b, s, h, d), (b, s, hkv, d), (b, s, hkv, dv))))
        kernel = jax.jit(lambda q, k, v, w=window: ak.prefill_attention(
            q, k, v, w, lengths, kernel=_single_tpu()))
        calls += _custom_calls(kernel.lower(q, k, v).compile())
        got = np.asarray(kernel(q, k, v), np.float32)
        ref = jax.jit(lambda q, k, v, w=window: ak._xla_prefill_attention(
            q, k, v, w, lengths))(q, k, v)
        diffs.append(_rel_diff(got, ref))
        padding_zero &= all(not got[row, n:].any()
                            for row, n in enumerate(cfg["lengths"]))
    tol = 2e-2
    expected = len(cfg["shapes"]) if _kernels_expected() else 0
    return {
        "ok": max(diffs) <= tol and padding_zero and calls == expected,
        "compared": "prefill_attention vs _xla_prefill_attention at "
                    "ragged lengths (max rel diff a head shape)",
        "max_diff": max(diffs), "diffs": diffs, "tol": tol,
        "padding_zero": bool(padding_zero),
        "custom_calls": calls, "custom_calls_expected": expected,
        "peak_bytes_in_use": _peak_bytes(),
    }


def phase_lm_serve(size: dict, seed: int) -> dict:
    import http.client

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.core import telemetry
    from mmlspark_tpu.models.generation import generate
    from mmlspark_tpu.models.transformer import _single_tpu
    from mmlspark_tpu.serving import read_stream

    cfg, lm = size["lm_serve"], size["lm"]
    n_new = cfg["max_new"]
    model = _lm(lm, jnp.float32)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, lm["vocab_size"], size=n).tolist()
               for n in cfg["prompt_lens"] for _ in range(cfg["copies"])]
    variables = {"params": jax.jit(
        lambda r, t: model.init(r, t)["params"])(
            jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))}

    threads_before = set(threading.enumerate())
    sentry = telemetry.track_compiles()
    sentry.reset()
    query = (read_stream()
             .continuous_server(name="chip-smoke-lm", path="/generate")
             .parse_request(schema=["prompt"])
             .generate_stream(model, variables, max_new_tokens=n_new,
                              max_slots=cfg["max_slots"], paged=True,
                              page_size=cfg["page_size"])
             .options(batch_timeout_ms=5.0)
             .start())
    info = query.service_info
    batcher = query._batcher
    served = [None] * len(prompts)
    errors = []

    def client(i):
        try:
            conn = http.client.HTTPConnection(info.host, info.port,
                                              timeout=600)
            conn.request("POST", info.path,
                         body=json.dumps({"prompt": prompts[i]}).encode())
            resp = conn.getresponse()
            body = resp.read().decode()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {body[:200]}")
            served[i] = [int(t) for t in body.split()]
            conn.close()
        except Exception as e:  # noqa: BLE001 — re-raised below, by request
            errors.append((i, repr(e)))

    try:
        # declared warmup: every (prompt bucket, padded row count) an
        # admission of this request set can form — the concurrent wave's
        # grouping is a race, so each shape must compile here.  One
        # thread's back-to-back submits land in one admission; the
        # prefill's own count of compiled shapes says when all were met.
        by_bucket = collections.defaultdict(list)
        for p in prompts:
            by_bucket[batcher._bucket(len(p))].append(p)
        waves = [group[:rows] for group in by_bucket.values()
                 for rows in (1, 2, 4, 8, 16, 32) if rows < 2 * len(group)]
        for _ in range(5):
            for wave in waves:
                for stream in [batcher.submit(p, max_new_tokens=2)
                               for p in wave]:
                    stream.tokens()
            if batcher._prefill_first._cache_size() >= len(waves):
                break
        else:
            raise RuntimeError("warmup never formed every admission shape")
        # then the decode program's evidence and the references (they
        # compile too)
        # (the step as the loop calls it: the step before's tokens and
        # positions, an override, the admissions' first tokens, tables)
        decode_calls = _custom_calls(batcher._step.lower(
            batcher.variables, batcher._cache, batcher._d_out,
            batcher._d_pos, batcher._keep, batcher._none,
            batcher._d_tables).compile())
        reference = jax.jit(lambda v, t: generate(model, v, t, n_new))
        want = [np.asarray(reference(variables, jnp.asarray([p], jnp.int32))
                           )[0, len(p):].tolist() for p in prompts]

        def hot_path_compiles():
            return telemetry.counters("xla.compile.hot_path").get(
                "xla.compile.hot_path", 0)

        sentry.end_warmup()
        hot_before = hot_path_compiles()
        clients = [threading.Thread(target=client, args=(i,), daemon=True,
                                    name=f"chip-smoke-client-{i}")
                   for i in range(len(prompts))]
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=600)
        hot_recompiles = hot_path_compiles() - hot_before
    finally:
        sentry.reset()
        query.stop()
    if errors or any(s is None for s in served):
        raise RuntimeError(f"clients failed or hung: {errors[:3]}")
    deadline = time.monotonic() + 5.0
    while True:
        leaked = [t.name for t in threading.enumerate()
                  if t not in threads_before and t.is_alive()
                  and not t.daemon]
        if not leaked or time.monotonic() > deadline:
            break
        time.sleep(0.05)

    exact = sum(g == w for g, w in zip(served, want))
    # Where a served token differs from generate's, ask the plain full
    # forward how far below its own best logit the served token sits at
    # that step: rounding leaves it within a hair, an indexing fault
    # (wrong page, wrong position) leaves it anywhere.
    width = max(len(p) for p in prompts) + n_new
    padded = np.zeros((len(prompts), width), np.int32)
    for i, (p, g) in enumerate(zip(prompts, served)):
        padded[i, :len(p) + len(g)] = p + g
    dense = _lm(lm, jnp.float32, _causal_dense)
    logits = np.asarray(jax.jit(
        lambda v, t: dense.apply(v, t)[0])(variables, jnp.asarray(padded)))
    margin = 0.0
    for i, (p, g) in enumerate(zip(prompts, served)):
        for j, tok in enumerate(g):
            row = logits[i, len(p) + j - 1]
            margin = max(margin, float(row.max() - row[tok]))
    margin_tol = 5e-2
    ok = (all(len(g) == n_new for g in served)
          and (exact == len(prompts) or margin <= margin_tol)
          and hot_recompiles == 0 and not leaked
          and decode_calls == (lm["num_layers"] if _kernels_expected()
                               else 0))
    return {
        "ok": ok,
        "compared": "HTTP completions vs generate() token for token; "
                    "served tokens' logit margin under the plain forward",
        "exact_match": f"{exact}/{len(prompts)}",
        "max_diff": margin, "tol": margin_tol,
        "requests": len(prompts), "new_tokens": n_new,
        "decode_custom_calls": decode_calls,
        "decode_custom_calls_expected": lm["num_layers"],
        "kernels_expected": _kernels_expected(),
        "paged_branch": "pallas" if _single_tpu() else "xla-gather",
        "hot_path_recompiles": hot_recompiles,
        "leaked_threads": leaked,
    }


def phase_lm3d(size: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mmlspark_tpu.models.training import (lm_params_to_3d,
                                              make_lm_train_step_3d,
                                              shard_params)
    from mmlspark_tpu.parallel.mesh import MeshContext, MeshPlan, make_mesh
    from mmlspark_tpu.parallel.sharding_rules import lm_3d_rules

    cfg, lm = size["lm3d"], size["lm"]
    a, m, mb, s, steps = (cfg[k] for k in
                          ("accum", "micro", "mb", "seq", "steps"))
    layers = lm["num_layers"]
    model = _lm(dict(lm, max_len=s), jnp.bfloat16)
    devices = jax.devices()[:4]
    plan = MeshPlan(data=2, model=2, pipe=1, devices=devices)
    one = make_mesh(devices=devices[:1])
    rng = jax.random.PRNGKey(seed)
    host_tokens = np.asarray(jax.random.randint(
        rng, (steps, a * m * mb, s), 0, lm["vocab_size"], jnp.int32))
    with MeshContext(one):
        params = jax.jit(lambda r, t: model.init(r, t)["params"])(
            rng, jnp.asarray(host_tokens[0, :2]))
    host_params = jax.tree.map(np.asarray, params)
    del params
    opt = optax.sgd(0.1)

    with MeshContext(plan.mesh):
        p3 = shard_params(lm_params_to_3d(host_params, layers, 1), plan.mesh,
                          lm_3d_rules())
        o3 = opt.init(p3)
        step = make_lm_train_step_3d(model, opt, plan, remat=True,
                                     donate=False)
        shaped = host_tokens.reshape(steps, a, m, mb, s)
        text = step.lower(p3, o3, shaped[0]).compile().as_text()
        losses = []
        for i in range(steps):
            p3, o3, metrics = step(p3, o3, shaped[i])
            losses.append(float(metrics["loss"]))
        # the step leaves its outputs' shardings to the compiler; where
        # they differ from the rule table's, the next call compiles again
        step_programs = step._cache_size()
        shard_devices = sorted({sh.device.id for leaf in jax.tree.leaves(p3)
                                for sh in leaf.addressable_shards})
        bytes_in_use = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                        for d in devices}
    del p3, o3

    # the one-device step on the same tokens (tools/parity3d.py's
    # reference), declared one-device so it keeps its kernels
    with MeshContext(one):
        p1 = jax.device_put(host_params, devices[0])
        o1 = opt.init(p1)

        @jax.jit
        def ref_step(p, o, t):
            def loss_fn(p):
                logits, _ = model.apply({"params": p}, t)
                return jnp.mean(
                    optax.softmax_cross_entropy_with_integer_labels(
                        logits[:, :-1].astype(jnp.float32), t[:, 1:]))

            loss, grads = jax.value_and_grad(loss_fn)(p)
            up, o = opt.update(grads, o, p)
            return optax.apply_updates(p, up), o, loss

        ref_losses = []
        for i in range(steps):
            p1, o1, loss = ref_step(p1, o1, jnp.asarray(host_tokens[i]))
            ref_losses.append(float(loss))

    diff = max(abs(x - y) for x, y in zip(losses, ref_losses))
    tol = 2e-2   # tools/parity3d.py's bf16 accumulation-order tolerance
    used = [b for b in bytes_in_use.values() if b is not None]
    balanced = (not used) or (min(used) > 0 and max(used) <= 1.5 * min(used))
    ok = (bool(np.isfinite(losses).all()) and diff <= tol
          and len(shard_devices) == 4 and balanced)
    return {
        "ok": ok,
        "compared": "per-step loss vs the one-device step on the same "
                    "tokens (max abs diff)",
        "max_diff": diff, "tol": tol,
        "losses": losses, "ref_losses": ref_losses,
        "mesh": dict(plan.shape),
        "param_shard_devices": shard_devices,
        "bytes_in_use": bytes_in_use,
        "collectives": dict(collections.Counter(_COLLECTIVES.findall(text))),
        "step_programs_compiled": step_programs,
    }


ONE_CHIP_PHASES = (("featurize", phase_featurize), ("vit", phase_vit),
                   ("lm_train", phase_lm_train),
                   ("admit_attn", phase_admit_attn),
                   ("lm_serve", phase_lm_serve))
FOUR_CHIP_PHASES = (("lm3d", phase_lm3d),)


def select_phases(chips: int):
    """`--chips 4` runs the multi-chip phase and what it is compared
    with, and no one-chip phase."""
    return FOUR_CHIP_PHASES if chips == 4 else ONE_CHIP_PHASES


def run_phases(phases, size: dict, seed: int, sentry=None) -> bool:
    """Run phases in order, one JSON line each; stop at the first that
    fails so no later line can say ok.  With the compile sentry, a line
    also says what its phase spent compiling or fetching executables
    and how the persistent cache answered."""
    totals = sentry.totals if sentry else collections.Counter
    for name, fn in phases:
        c0 = totals()
        t0 = time.perf_counter()
        try:
            rec = fn(size, seed)
        except Exception as e:  # noqa: BLE001 — named, printed, fatal
            import traceback

            traceback.print_exc()
            rec = {"ok": False, "error": f"{type(e).__name__}: {e}"[-2000:]}
        c1 = totals()
        print(json.dumps({
            "phase": name, **rec,
            "compile_s": round(c1["compile_s"] - c0["compile_s"], 3),
            **{f"cache_{k}": c1[k] - c0[k]
               for k in ("hits", "misses", "unwritten")},
            "wall_s": round(time.perf_counter() - t0, 3)}), flush=True)
        if not rec["ok"]:
            return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase (lm3d)")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, tokens and images are made from it")
    args = ap.parse_args(argv)

    from mmlspark_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r} "
              f"({len(devices)} device(s)); there is no CPU mode",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(json.dumps({"phase": "setup", "device": device,
                      "compile_cache_dir": cache_dir,
                      "jax": jax.__version__, "seed": args.seed}), flush=True)
    from mmlspark_tpu.core import telemetry

    sentry = telemetry.track_compiles()
    ok = run_phases(select_phases(args.chips), SIZES["full"], args.seed,
                    sentry)
    # set-up is what ran before the first warm-up was declared over
    print(json.dumps({"setup_programs": sentry.report()}), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
