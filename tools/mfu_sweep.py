"""MFU campaign driver: sweep batch size / dtype / XLA flags on the real
chip and print one JSON line per config.

XLA flags only apply at backend init, so every config runs in a fresh
subprocess — one at a time, and this parent never imports jax, so each
child holds the chip alone (one process per chip).  Usage:

    python tools/mfu_sweep.py              # the standard sweep
    python tools/mfu_sweep.py --quick      # batch sweeps only (resnet50 + vit)

Results feed docs/performance.md's roofline section; tools/roofline.py
computes the analytic ceiling these numbers are judged against.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _bench_ms(fn, *args, iters: int = 5, reps: int = 3) -> float:
    """Best-of-`reps` wall time of `iters` dispatches, ms per call —
    bench.py's `_best_of` (the single timing methodology), in ms units."""
    from bench import _best_of

    return 1000.0 * _best_of(lambda: fn(*args), iters, reps) / iters


CONFIGS = [
    # (tag, batch, extra XLA flags, builder)
    ("b128", 128, "", "resnet50"),
    ("b256", 256, "", "resnet50"),
    ("b512", 512, "", "resnet50"),
    ("b256-latency-hiding", 256,
     "--xla_tpu_enable_latency_hiding_scheduler=true", "resnet50"),
    ("b256-async-all", 256,
     "--xla_enable_async_all_gather=true", "resnet50"),
    # ViT-B is the matmul-dominated vision backbone: this is where the
    # >=0.5 MFU the CNN roofline forbids is actually available
    ("vit-b128", 128, "", "vit_base"),
    ("vit-b256", 256, "", "vit_base"),
    # int8 PTQ encoder matmuls (ops/quant.py): ips is the headline here;
    # "mfu" stays normalized to the bf16 peak, so >1.0 is possible
    ("vit-b128-int8", 128, "", "vit_base_int8"),
]
QUICK = {"b128", "b256", "b512", "vit-b128", "vit-b256", "vit-b128-int8"}


def child(batch: int, builder: str = "resnet50") -> int:
    """Runs in the measurement subprocess: jitted bf16 backbone forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import _chip_peak_flops
    from mmlspark_tpu.models.bundle import FlaxBundle

    kwargs = {"num_classes": 1000}
    base = builder
    if builder.endswith("_int8"):
        base = builder[: -len("_int8")]
        kwargs["quant"] = True
    side, iters = 224, 10
    smoke = bool(os.environ.get("MFU_SWEEP_SMOKE"))
    if smoke:
        # CPU contract smoke (tests/test_sweep_contract.py): same code path
        # — FlaxBundle, quant branch, cost_analysis, timing, JSON shape —
        # on a sibling backbone tiny enough for the CPU backend; batches
        # stay distinct (128/256/512 -> 1/2/4) so the sweep loop is still
        # a real batch sweep, not three duplicate children
        base = {"resnet50": "resnet18", "vit_base": "vit_tiny"}.get(base, base)
        batch, side, iters = max(1, batch // 128), 32, 1
    bundle = FlaxBundle(base, kwargs, input_shape=(side, side, 3))
    if kwargs.get("quant"):
        # the int8 path's deployment contract is the UNCHANGED f32 pytree
        # (ops/quant.py) — casting to bf16 here would halve weight reads
        # and change numerics vs what quant=True actually ships
        dev_vars = jax.device_put(bundle.variables)
    else:
        dev_vars = jax.device_put(jax.tree.map(
            lambda x: jnp.asarray(x, jnp.bfloat16), bundle.variables))

    def forward(v, x):
        return bundle.apply(v, x)["pool"]

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, side, side, 3)), jnp.bfloat16)
    compiled = jax.jit(forward).lower(dev_vars, x).compile()
    cost = compiled.cost_analysis()
    flops = float(cost["flops"])
    bytes_acc = float(cost["bytes accessed"])
    ms = _bench_ms(compiled, dev_vars, x, iters=iters)
    kind = jax.devices()[0].device_kind
    # a CPU smoke has no peak to be measured against (and says so: mfu
    # null); on a chip an unknown device kind raises
    peak = None if smoke else _chip_peak_flops()
    print(json.dumps({
        # a smoke record must be self-identifying: it measured the tiny
        # sibling (resnet18/vit_tiny @ 32px), not the labeled builder
        **({"smoke": True, "smoke_builder": base, "smoke_side": side}
           if smoke else {}),
        "builder": builder,
        "batch": batch,
        "ips": round(1000.0 * batch / ms, 1),
        "ms_per_batch": round(ms, 2),
        "mfu": round(1000.0 * flops / ms / peak, 4) if peak else None,
        "xla_flops": flops,
        "xla_bytes": bytes_acc,
        "arith_intensity": round(flops / bytes_acc, 1) if bytes_acc else None,
        "device": kind,
    }))
    return 0


def attn_child() -> int:
    """Pallas fused_attention vs XLA dense forward, several (S, D) points
    — run on the real chip to validate the Mosaic compile AND quantify
    the win.  Parity vs the dense reference is ENFORCED (nonzero exit on
    divergence), so a recorded sweep is validation evidence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.ops import attention_kernels as ak
    from mmlspark_tpu.ops.attention_kernels import fused_attention
    from mmlspark_tpu.parallel.ring_attention import full_attention

    backend = jax.default_backend()

    rng = np.random.default_rng(0)
    failures = 0
    # (196, 64, 12, non-causal) is the ViT-B shape AS ViT RUNS IT: S pads
    # 196->256 under kv_valid masking, bidirectional attention — the point
    # measures whether the padded kernel beats XLA dense on the one
    # production shape that needs padding, with the mask ViT exercises
    points = [(196, 64, 12, False), (1024, 64, 12, True),
              (2048, 128, 8, True), (4096, 128, 8, True)]
    if os.environ.get("ATTN_SWEEP_POINTS"):
        # smoke override: "s:d:h" (causal) or "s:d:h:0" (non-causal) —
        # the 4th field lets smoke cover the kv_valid/bidirectional branch
        def _parse(p):
            f = p.split(":")
            return (int(f[0]), int(f[1]), int(f[2]),
                    bool(int(f[3])) if len(f) > 3 else True)
        points = [_parse(p)
                  for p in os.environ["ATTN_SWEEP_POINTS"].split(",")]
    for s, d, h, causal in points:
        q, k, v = (jnp.asarray(rng.normal(size=(4, s, h, d)), jnp.bfloat16)
                   for _ in range(3))
        fns = {"pallas": jax.jit(
                   lambda q, k, v: fused_attention(q, k, v, causal)),
               "xla": jax.jit(
                   lambda q, k, v: full_attention(q, k, v, causal=causal))}
        # record which path 'pallas' ACTUALLY takes — parity of the XLA
        # composition against itself proves nothing about the kernel
        kernel_runs = bool(ak.kernel_ok(q))
        rec = {**({"smoke": True} if os.environ.get("ATTN_SWEEP_POINTS")
                  else {}),
               "seq": s, "head_dim": d, "heads": h, "causal": causal,
               "backend": backend,
               "pallas_path": ("mosaic" if kernel_runs and backend == "tpu"
                               else "interpret" if kernel_runs
                               else "xla"),
               # the head-dim the kernels tile at: d itself for
               # 64-multiples (native), else padded up to the 128 lane
               "kernel_d": (ak._kernel_d(d) if kernel_runs else None),
               # set ONLY after the kernel actually compiled, ran, and
               # matched — a thrown compile must not read as validated
               "mosaic_validated": False}
        outs = {}
        try:
            for name, fn in fns.items():
                outs[name] = fn(q, k, v)
                rec[f"{name}_ms"] = round(_bench_ms(fn, q, k, v), 3)
            err = float(jnp.max(jnp.abs(outs["pallas"] - outs["xla"])))
            rec["max_abs_diff"] = round(err, 5)
            # a recorded sweep IS the validation evidence: enforce parity
            rec["parity_ok"] = err < 0.02
            rec["mosaic_validated"] = (kernel_runs and backend == "tpu"
                                       and rec["parity_ok"])
            failures += 0 if rec["parity_ok"] else 1
            rec["speedup"] = round(rec["xla_ms"] / rec["pallas_ms"], 2)
            # flash BACKWARD: validate the dK/dV + dQ kernels under the
            # same Mosaic compile and quantify them vs the dense-XLA
            # gradient.  The dense reference materializes f32 [B,H,S,S]
            # score tensors — skip it at s=4096 (multi-GB per tensor,
            # OOM territory on one chip) and record kernel timing alone.
            if kernel_runs:
                loss_k = lambda q, k, v: jnp.sum(
                    fused_attention(q, k, v, causal).astype(
                        jnp.float32) ** 2)
                loss_x = lambda q, k, v: jnp.sum(
                    full_attention(q, k, v, causal=causal).astype(
                        jnp.float32) ** 2)
                gfn = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))
                rec["bwd_pallas_ms"] = round(
                    _bench_ms(lambda q, k, v: gfn(q, k, v)[0], q, k, v), 3)
                if s <= 2048:
                    gref = jax.jit(jax.grad(loss_x, argnums=(0, 1, 2)))
                    g, gr = gfn(q, k, v), gref(q, k, v)
                    rel = max(
                        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                              - b.astype(jnp.float32)))
                              / (jnp.max(jnp.abs(
                                  b.astype(jnp.float32))) + 1e-6))
                        for a, b in zip(g, gr))
                    del g, gr
                    rec["bwd_max_rel_diff"] = round(rel, 5)
                    rec["bwd_parity_ok"] = rel < 0.05
                    # backward divergence un-validates the point: the
                    # field means "compiled, ran, AND matched" for every
                    # kernel the path commits callers to
                    rec["mosaic_validated"] = (rec["mosaic_validated"]
                                               and rec["bwd_parity_ok"])
                    failures += 0 if rec["bwd_parity_ok"] else 1
                    rec["bwd_xla_ms"] = round(
                        _bench_ms(lambda q, k, v: gref(q, k, v)[0],
                                  q, k, v), 3)
                    rec["bwd_speedup"] = round(
                        rec["bwd_xla_ms"] / rec["bwd_pallas_ms"], 2)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            rec["error"] = str(e)[-300:]
            failures += 1
        print(json.dumps(rec))
    return 1 if failures else 0


def decode_child() -> int:
    """Batch-1 KV-cached decode tokens/sec: f32 weights vs prequantized
    int8 (ops/quant.prequantize).  Decode is weight-bandwidth-bound, so
    the int8/f32 ratio measures realized HBM savings (~4x bytes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.generation import generate
    from mmlspark_tpu.models.transformer import transformer_lm
    from mmlspark_tpu.ops.quant import prequantize

    cfg = dict(vocab_size=8192, embed_dim=768, num_layers=12, num_heads=12,
               max_len=512)
    if os.environ.get("DECODE_SWEEP_SMALL"):  # CPU smoke override
        cfg = dict(vocab_size=256, embed_dim=64, num_layers=2, num_heads=2,
                   max_len=64)
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], size=(1, 16)), jnp.int32)
    new_tokens = cfg["max_len"] - 32
    results = {}
    for tag, quant, kv, kvh in (("f32", False, None, None),
                                ("int8", True, None, None),
                                ("int8_kv8", True, "int8", None),
                                ("gqa4", False, None, "quarter")):
        kv_heads = max(1, cfg["num_heads"] // 4) if kvh else None
        model = transformer_lm(dtype=jnp.float32, quant=quant,
                               num_kv_heads=kv_heads, **cfg)
        variables = {c: v for c, v in jax.jit(
            lambda r, t: model.init(r, t))(
                jax.random.PRNGKey(0), prompt).items() if c != "kvcache"}
        if quant:
            variables = prequantize(model, variables, prompt)
        run = jax.jit(lambda v, p, _m=model, _kv=kv: generate(
            _m, v, p, new_tokens, kv_cache_dtype=_kv))
        ms = _bench_ms(run, variables, prompt, iters=1)
        results[f"decode_tok_per_sec_{tag}"] = round(1000.0 * new_tokens / ms, 1)
    results["int8_speedup"] = round(
        results["decode_tok_per_sec_int8"] / results["decode_tok_per_sec_f32"], 2)

    # paged-attention kernel: Mosaic compile + parity + page-walk timing
    # vs the XLA gather at a long-context shape (the read-bandwidth case
    # paging exists for: 2 live pages out of 32)
    from mmlspark_tpu.ops.paged_attention import (
        _paged_pallas, _xla_paged, paged_kernel_ok)

    rng = np.random.default_rng(1)
    h, d, page, mp, np_, nb = 12, 64, 64, 32, 40, 8
    if os.environ.get("DECODE_SWEEP_SMALL"):  # CPU interpret-mode cost
        h, d, page, mp, np_, nb = 2, 64, 8, 4, 6, 2
    q = jnp.asarray(rng.normal(size=(nb, h, d)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(np_, page, h * d)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(np_, page, h * d)), jnp.bfloat16)
    tbl = jnp.asarray(np.tile(np.arange(mp) % (np_ - 1) + 1, (nb, 1)),
                      jnp.int32).at[:, 2:].set(0)  # 2 live pages/slot
    pos = jnp.full((nb,), 2 * page - 1, jnp.int32)
    assert paged_kernel_ok(q, kp)  # shapes chosen kernel-eligible
    got = _paged_pallas(q, kp, vp, tbl, pos)
    ref = _xla_paged(q, kp, vp, tbl, pos)
    err = float(jnp.max(jnp.abs(got - ref)))
    results["paged_kernel_max_abs_diff"] = round(err, 5)
    results["paged_kernel_parity_ok"] = err < 0.05
    results["paged_kernel_validated"] = (
        jax.default_backend() == "tpu" and err < 0.05)
    results["paged_kernel_ms"] = round(_bench_ms(
        jax.jit(_paged_pallas), q, kp, vp, tbl, pos, iters=20), 3)
    results["paged_gather_ms"] = round(_bench_ms(
        jax.jit(_xla_paged), q, kp, vp, tbl, pos, iters=20), 3)

    results["device"] = jax.devices()[0].device_kind
    if os.environ.get("DECODE_SWEEP_SMALL"):
        results["smoke"] = True
    print(json.dumps(results))
    return 0


def batcher_child() -> int:
    """Continuous-batching decode throughput: aggregate tokens/sec with 1
    vs 8 concurrent streams on the slotted step — the serving-side
    scaling evidence (per-tick cost is one batched decode_step, so
    tokens/sec should rise ~linearly with co-tenant streams until the
    chip saturates)."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.transformer import transformer_lm
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    cfg = dict(vocab_size=8192, embed_dim=768, num_layers=12, num_heads=12,
               max_len=512)
    if os.environ.get("DECODE_SWEEP_SMALL"):  # CPU smoke override
        cfg = dict(vocab_size=256, embed_dim=64, num_layers=2, num_heads=2,
                   max_len=128)
    model = transformer_lm(dtype=jnp.float32, **cfg)
    prompt = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], size=(16,))
    variables = {c: v for c, v in jax.jit(
        lambda r, t: model.init(r, t))(
            jax.random.PRNGKey(0),
            jnp.asarray(prompt[None], jnp.int32)).items() if c != "kvcache"}
    n_new = 64
    results = {}
    spec_draft = None
    for tag, n_streams, kw in (
            ("1_streams", 1, {}),
            ("8_streams", 8, {}),
            # paged KV at the same co-tenancy: throughput delta vs the
            # dense slot cache, with the pool sized to the WORKLOAD
            # (Σ worst-case pages) instead of max_slots * max_len — the
            # density the paging buys is the kv_hbm_bytes ratio below
            ("8_streams_paged", 8, {"paged": True, "page_size": 64}),
            # speculative continuous batching with the int8 self-draft
            # (near-perfect acceptance, 1/4-bandwidth draft steps): the
            # per-tick target forward amortizes over up to gamma+1 tokens
            ("8_streams_spec", 8, {"spec": True}),
    ):
        if kw.pop("spec", False):
            if spec_draft is None:
                from mmlspark_tpu.ops.quant import prequantize

                dm = transformer_lm(dtype=jnp.float32, quant=True, **cfg)
                spec_draft = (dm, prequantize(
                    dm, dict(variables),
                    jnp.asarray(prompt[None], jnp.int32)))
            kw = dict(draft_model=spec_draft[0],
                      draft_variables=spec_draft[1], gamma=4)
        if kw.get("paged"):
            worst = -(-(len(prompt) + n_new) // kw["page_size"])
            kw["num_pages"] = 8 * worst + 2  # workload-sized pool (+warm)
        batcher = ContinuousBatcher(model, variables,
                                    max_slots=max(n_streams, 1), **kw).start()
        try:
            # warm: compile prefill + step
            batcher.submit(prompt, max_new_tokens=2).tokens()
            t0 = _time.perf_counter()
            streams = [batcher.submit(prompt, max_new_tokens=n_new)
                       for _ in range(n_streams)]
            total = sum(len(s.tokens()) for s in streams)
            dt = _time.perf_counter() - t0
        finally:
            batcher.stop()
        results[f"tok_per_sec_{tag}"] = round(total / dt, 1)
        results[f"kv_hbm_bytes_{tag}"] = sum(
            int(leaf.size) * leaf.dtype.itemsize
            for layer in batcher._cache for leaf in layer)
    results["batching_speedup"] = round(
        results["tok_per_sec_8_streams"] / results["tok_per_sec_1_streams"], 2)
    results["paged_throughput_ratio"] = round(
        results["tok_per_sec_8_streams_paged"]
        / results["tok_per_sec_8_streams"], 2)
    results["spec_throughput_ratio"] = round(
        results["tok_per_sec_8_streams_spec"]
        / results["tok_per_sec_8_streams"], 2)
    results["paged_hbm_ratio"] = round(
        results["kv_hbm_bytes_8_streams_paged"]
        / results["kv_hbm_bytes_8_streams"], 3)
    results["device"] = jax.devices()[0].device_kind
    if os.environ.get("DECODE_SWEEP_SMALL"):
        results["smoke"] = True
    print(json.dumps(results))
    return 0


def serving_child() -> int:
    """BASELINE.json config 5: a continuous-batched ResNet-50
    ImageFeaturizer endpoint with the accelerator IN the loop — clients
    POST base64 JPEGs over keep-alive loopback HTTP, the server drains
    opportunistic batches, decodes natively, featurizes on device
    (pad_to_batch: one compiled shape forever), replies the 2048-d pooled
    vector.  Prints p50/p99/QPS; the chip row for benchmarks_serving.csv."""
    import base64
    import http.client
    import threading
    import time as _time

    import numpy as np

    import bench as _bench
    from mmlspark_tpu.core.pipeline import LambdaTransformer, PipelineModel
    from mmlspark_tpu.models.bundle import FlaxBundle
    from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
    from mmlspark_tpu.serving.server import ServingServer

    import jax

    n_clients, per_client = 8, 25
    backbone, side, max_batch = "resnet50", 224, 32
    if os.environ.get("SERVING_SWEEP_SMALL"):  # CPU smoke override
        # tiny sibling backbone: same endpoint path (decode -> resize ->
        # padded batch forward -> tap reply) at CPU-smoke cost
        n_clients, per_client = 2, 4
        backbone, side, max_batch = "resnet18", 32, 4

    bundle = FlaxBundle(backbone, {"num_classes": 1000},
                        input_shape=(side, side, 3))
    feat = ImageFeaturizer(bundle=bundle, input_col="image_bytes",
                           output_col="features", batch_size=max_batch,
                           pad_to_batch=True)
    b64_decode = LambdaTransformer(lambda t: t.with_column(
        "image_bytes", np.asarray(
            [base64.b64decode(s) for s in t["image"]], dtype=object)))
    srv = ServingServer(model=PipelineModel([b64_decode, feat]),
                        reply_col="features", name="img", path="/featurize",
                        max_batch=max_batch, batch_timeout_ms=5.0)
    info = srv.start()

    jpeg = bytes(_bench._synthetic_jpeg_table(1)["image"][0])
    body = json.dumps({"image": base64.b64encode(jpeg).decode()}).encode()
    hdrs = {"Content-Type": "application/json"}
    lat = np.zeros((n_clients, per_client))
    errors = []

    def client(ci):
        try:
            conn = http.client.HTTPConnection(info.host, info.port)
            for i in range(per_client):
                t0 = _time.perf_counter()
                conn.request("POST", "/featurize", body, hdrs)
                resp = conn.getresponse()
                payload = resp.read()
                lat[ci, i] = _time.perf_counter() - t0
                assert resp.status == 200, (resp.status, payload[:200])
            conn.close()
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append((ci, repr(e)))

    try:
        # warm: compiles the single padded [32,224,224,3] program
        wconn = http.client.HTTPConnection(info.host, info.port)
        wconn.request("POST", "/featurize", body, hdrs)
        assert wconn.getresponse().read()
        wconn.close()
        t0 = _time.perf_counter()
        threads = [threading.Thread(target=client, args=(ci,), daemon=True,
                                    name=f"mfu-sweep-client-{ci}")
                   for ci in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        wall = _time.perf_counter() - t0
    finally:
        srv.stop()
    if errors or not np.all(lat > 0):
        print(json.dumps({"error": f"clients failed/hung: {errors[:3]}"}))
        return 1
    flat = lat.reshape(-1) * 1000.0
    print(json.dumps({
        **({"smoke": True} if os.environ.get("SERVING_SWEEP_SMALL") else {}),
        "serving_chip_p50_ms": round(float(np.percentile(flat, 50)), 2),
        "serving_chip_p99_ms": round(float(np.percentile(flat, 99)), 2),
        "serving_chip_qps": round(n_clients * per_client / wall, 1),
        "batches": srv.stats["batches"],
        "requests": srv.stats["requests"],
        "device": jax.devices()[0].device_kind,
    }))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--attn", action="store_true",
                    help="fused_attention vs XLA dense on the chip")
    ap.add_argument("--decode", action="store_true",
                    help="batch-1 decode tokens/sec, f32 vs prequant int8")
    ap.add_argument("--batcher", action="store_true",
                    help="continuous-batching tokens/sec, 1 vs 8 streams")
    ap.add_argument("--serving", action="store_true",
                    help="ResNet-50 featurizer endpoint p50/p99/QPS, "
                         "accelerator in the loop")
    ap.add_argument("--child", type=int, default=None)
    ap.add_argument("--builder", default="resnet50")
    args = ap.parse_args()
    if args.child is not None:
        return child(args.child, args.builder)
    if args.attn:
        return attn_child()
    if args.decode:
        return decode_child()
    if args.batcher:
        return batcher_child()
    if args.serving:
        return serving_child()
    for tag, batch, flags, builder in CONFIGS:
        if args.quick and tag not in QUICK:
            continue
        env = dict(os.environ)
        if flags:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flags).strip()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--child", str(batch), "--builder", builder],
                env=env, capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            print(json.dumps({"tag": tag, "error": "timeout"}))
            continue
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        try:
            rec = json.loads(line)
            rec["tag"] = tag
            if flags:
                rec["xla_flags"] = flags
        except json.JSONDecodeError:
            rec = {"tag": tag, "error": (proc.stderr or "no output")[-300:]}
        print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
